"""Finding a cell's files by the names in ``BENCHMARK.json``."""

import importlib.util
import json
from pathlib import Path
from typing import Dict, List


class Bench:
    """``root`` holds ``BENCHMARK.json`` and the ``cellbench/`` data
    directories (``configs/``, ``traffic/``, ``layer_metrics/``,
    ``counts/``) — the checkout, or a directory a test has filled."""

    def __init__(self, root):
        self.root = Path(root)
        with open(self.root / "BENCHMARK.json") as f:
            self.spec = json.load(f)
        self.data = self.root / "cellbench"

    def _json(self, path: Path) -> Dict:
        with open(path) as f:
            return json.load(f)

    def cell(self, name: str) -> Dict:
        rows = [w for w in self.spec["workloads"] if w["name"] == name]
        if not rows:
            known = [w["name"] for w in self.spec["workloads"]]
            raise SystemExit(f"cellbench: no workload {name!r}; "
                             f"BENCHMARK.json has {known}")
        cell = dict(rows[0])
        conf = next(c for c in self.spec["configs"]
                    if c["name"] == cell["config"])
        cell["config_file"] = self._json(self.root / conf["file"])
        cell["traffic_file"] = self._json(
            self.data / "traffic" / f"{cell['traffic']}.json")
        return cell

    def _reports(self, metric: Dict, cell_name: str) -> bool:
        return "workloads" not in metric or cell_name in metric["workloads"]

    def end_to_end(self, cell_name: str) -> List[Dict]:
        return [m for m in self.spec["end_to_end"]
                if self._reports(m, cell_name)]

    def per_layer(self, cell_name: str) -> List[Dict]:
        """The cell's per-layer metrics: the entry of ``BENCHMARK.json``
        merged over its own file."""
        out = []
        for m in self.spec["per_layer"]:
            if not self._reports(m, cell_name):
                continue
            path = self.data / "layer_metrics" / f"{m['name']}.json"
            out.append({**self._json(path), **m})
        return out

    def _module(self, path: Path):
        if not path.exists():
            return None
        spec = importlib.util.spec_from_file_location(
            "cellbench_data_" + path.stem.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def custom_reader(self, metric_name: str):
        return self._module(
            self.data / "layer_metrics" / f"{metric_name}.py")

    def counts(self, name: str):
        mod = self._module(self.data / "counts" / f"{name}.py")
        if mod is None:
            raise FileNotFoundError(f"no cellbench/counts/{name}.py")
        return mod

    def peaks(self, device_kind: str) -> Dict:
        table = self._json(Path(__file__).parent / "peaks.json")
        if device_kind not in table:
            raise SystemExit(f"cellbench: device kind {device_kind!r} is not "
                             f"in peaks.json; add its published peaks")
        return table[device_kind]
