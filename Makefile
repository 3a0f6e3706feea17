# CI surface for apex_tpu — `make ci` is what .github/workflows/ci.yml
# runs, and what a laptop runs before pushing.  Two gates:
#
#   make test        tier-1 (quick) pytest suite on the 8-virtual-device
#                    CPU platform, six xdist workers — the command the
#                    driver judges the suite by, less its junit
#                    plumbing and the ALLOW_MULTIPLE_LIBTPU_LOAD=1 it
#                    sets for its own runs
#   make analyze     the static analyzer over its default operands
#                    (apex_tpu, examples), ONE scan doing both jobs:
#                    writes the SARIF document for code scanning
#                    (analysis.sarif — written before the exit code, so
#                    the upload has content exactly when there ARE
#                    findings) and fails on findings or stale
#                    suppressions (--check-baseline), with the
#                    human-readable rule-id summary on stderr; the
#                    per-rule timing JSON (analysis_timing.json) rides
#                    along so CI can attribute a slow scan to a rule
#
# Speed is not gated here: it is measured on the chip, one cell at a
# time (`python -m cellbench`, BENCHMARK.json, PERF.md).  See
# docs/static_analysis.md for analyzer details and the baseline
# contract.

PYTHON ?= python
JOBS   ?= 2

.PHONY: ci test analyze

ci: analyze test

test:
	timeout -k 10 1470 env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q \
	  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
	  -p xdist -n 6 --dist loadfile -p no:randomly

analyze:
	$(PYTHON) -m apex_tpu.analysis \
	  --format sarif --check-baseline --jobs $(JOBS) \
	  --timing-json analysis_timing.json > analysis.sarif
