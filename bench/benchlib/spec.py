"""Find a cell's files by the names in ``BENCHMARK.json``.

A configuration is the file its entry names; a traffic mix is
``<bench>/traffic/<traffic>.json``; a per-layer metric is the reader
``<bench>/metrics/<name>.py``, a module with ``read(ctx)`` that returns
the metric's value, or ``None`` where it finds nothing to read; a
configuration's plain reference is ``<bench>/references/<reference>.py``
and its architecture ``<bench>/archs/<arch>.py``, both named by the
configuration file; a kernel counter is any ``<bench>/kernels/<name>.py``,
found by the Pallas names it lists in ``KERNELS``. Adding any of them is
adding files.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: Path
    _arch: object = dataclasses.field(default=None, repr=False)

    def reader(self, metric: str) -> Callable:
        return load_module(self.bench_dir / "metrics" / f"{metric}.py").read

    def reference(self):
        return load_module(self.bench_dir / "references"
                           / f"{self.config['reference']}.py")

    def arch(self):
        """The configuration's architecture file, loaded once a cell."""
        if self._arch is None:
            self._arch = load_module(self.bench_dir / "archs"
                                     / f"{self.config['arch']}.py")
        return self._arch

    def kernels(self) -> Dict[str, object]:
        return kernel_counters(self.bench_dir)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel_counters(bench_dir: Path = BENCH) -> Dict[str, object]:
    """Kernel name -> the counter under ``<bench_dir>/kernels`` that lists
    it."""
    out: Dict[str, object] = {}
    for path in sorted((bench_dir / "kernels").glob("*.py")):
        mod = load_module(path)
        out.update((name, mod) for name in mod.KERNELS)
    return out


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str, reported: set) -> bool:
    """A per-layer metric is read in the cells it lists, or else in every
    cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in reported


def resolve(workload: str, root: Path = ROOT,
            bench: Dict = None, bench_dir: Path = None) -> Cell:
    bench = load_benchmark(root) if bench is None else bench
    bench_dir = BENCH if bench_dir is None else bench_dir
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, reported)]
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)
