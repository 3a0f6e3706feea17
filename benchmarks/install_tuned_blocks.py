"""Install flash_sweep.py results into the kernel's per-shape block table.

Reads a flash_sweep.py output file (JSONL; the last ``tuned_blocks_table``
line wins), merges it into the ``_TUNED_BLOCKS`` literal in
``apex_tpu/ops/flash_attention_pallas.py``, and rewrites the file — so the
measured defaults ship in source with their provenance, instead of living
only in a runtime ``set_tuned_blocks`` call someone has to remember.

    python benchmarks/install_tuned_blocks.py /tmp/runbook/flash_sweep.out \
        --provenance "v5e-lite 2026-07-31 flash_sweep"

Keys are per-phase ``(S, D, dtype, phase)`` with phase ∈ {"fwd", "bwd"}
(the forward and backward kernels consult separate entries); values are
``(bq, bk)`` or ``(bq, bk, sub)``, ``sub`` the side of the sub-tiles the
kernels walk a block in (absent: the kernels' own constant).  Old flat
3-element keys — in the sweep output OR already installed in the
source literal — migrate as ``"fwd"`` entries: pre-split sweeps
measured the forward dispatcher's path.

Idempotent: re-running with the same sweep output produces the same file.
"""

import argparse
import json
import re
from pathlib import Path

KERNEL = Path(__file__).resolve().parents[1] / "apex_tpu" / "ops" / "flash_attention_pallas.py"


def read_table(sweep_path: str):
    table = None
    with open(sweep_path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and "tuned_blocks_table" in rec:
                table = rec["tuned_blocks_table"]
    if table is None:
        raise SystemExit(f"no tuned_blocks_table line in {sweep_path}")
    return table


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("sweep_output")
    ap.add_argument("--provenance", required=True,
                    help="hardware + date string recorded above the table")
    args = ap.parse_args()
    if "}" in args.provenance or "{" in args.provenance:
        raise SystemExit("--provenance must not contain braces (it is "
                         "embedded in the rewritten dict literal)")

    src0 = KERNEL.read_text()
    m = re.search(r"_TUNED_BLOCKS: dict = \{(.*?)\}", src0, re.S)
    if m is None:
        raise SystemExit(f"_TUNED_BLOCKS literal not found in {KERNEL}")
    # merge with whatever is already installed (a narrower follow-up
    # sweep must not delete other shapes' measured defaults); parse the
    # literal with ast so hand-edits/reformatting can't be silently
    # dropped — anything unparseable fails loudly instead
    import ast

    body_src = "\n".join(ln for ln in m.group(1).splitlines()
                         if not ln.strip().startswith("#"))
    try:
        existing = ast.literal_eval("{" + body_src + "}")
    except (SyntaxError, ValueError) as e:
        raise SystemExit(
            f"could not parse the existing _TUNED_BLOCKS literal: {e}")
    def norm_key(key):
        """(S, D, dtype, phase) — 3-element keys (the pre-per-phase
        format, from old sweeps or an old installed literal) are
        forward measurements."""
        if len(key) == 3:
            s, d, dtype = key
            phase = "fwd"
        else:
            s, d, dtype, phase = key
        if phase not in ("fwd", "bwd"):
            raise SystemExit(f"bad tuned-block phase {phase!r} in {key!r}")
        return (int(s), int(d), str(dtype), str(phase))

    def norm_val(val):
        """(bq, bk) or (bq, bk, sub)."""
        if len(val) not in (2, 3):
            raise SystemExit(f"bad tuned-block row {val!r}: (bq, bk[, sub])")
        return tuple(int(x) for x in val)

    entries = {norm_key(k): norm_val(v) for k, v in existing.items()}
    for key, val in read_table(args.sweep_output):
        entries[norm_key(key)] = norm_val(val)
    if not entries:
        raise SystemExit("tuned_blocks_table was empty")

    body = "".join(
        f"    ({s}, {d}, {dtype!r}, {phase!r}): "
        f"({', '.join(str(x) for x in val)}),\n"
        for (s, d, dtype, phase), val in sorted(entries.items())
    )
    new_literal = (
        f"_TUNED_BLOCKS: dict = {{\n"
        f"    # measured: {args.provenance} (benchmarks/flash_sweep.py)\n"
        f"{body}}}"
    )

    pattern = re.compile(r"_TUNED_BLOCKS: dict = \{.*?\}", re.S)
    KERNEL.write_text(pattern.sub(new_literal.replace("\\", r"\\"), src0, count=1))
    print(f"installed {len(entries)} entries into {KERNEL}")


if __name__ == "__main__":
    main()
