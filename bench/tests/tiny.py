"""A Mixtral-shaped configuration at test size, laid out in a temporary
root as a later change would add one: a configuration file, a traffic
file and a BENCHMARK.json that names them."""
from __future__ import annotations

import json
from pathlib import Path

from benchlib import spec
from benchlib import traffic as T

MIXED = spec.BENCH / "configs" / "mixtral-8x7b-mixed.json"
#: the window of the runs in bench/tests/test_bench_check.py
CHECK_SECONDS = 0.5


def tiny_config(path: Path = MIXED) -> dict:
    """The configuration file at ``path`` at test widths."""
    c = json.loads(path.read_text())
    c.update({"hidden_size": 256, "intermediate_size": 512,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 64, "vocab_size": 1024, "page_size": 16,
              # at this size the program reads about 1e-3 and the
              # control about 1e-1 (bench/tests/test_bench_check.py)
              "check": {"p99_logit_gap_clear": 0.02}})
    c["plan"] = dict(c["plan"], group_size=16)
    c["program_departures"] = dict(c["program_departures"],
                                   embedding_multiplier=16.0, vocab_rows=2048)
    return c


TRAFFIC = {
    "closed": {"loop": "closed", "clients": 4,
               "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.8,
                          "min": 8, "max": 48},
               "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                          "min": 4, "max": 16},
               "slots": 4, "max_len": 64, "check_tokens": 200},
    # a burst of 30 requests due in CHECK_SECONDS, more than 4 slots serve
    # in that time, so that every slot decodes; check_tokens is every
    # answer's tokens, so the check waits for all of them and compares
    # the same requests whatever the host's speed
    "burst": {"loop": "open", "rate_per_s": 60.0,
              "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.8,
                         "min": 8, "max": 48},
              "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                         "min": 4, "max": 16},
              "slots": 4, "max_len": 64},
}
TRAFFIC["burst"]["check_tokens"] = int(T.quantile_lengths(
    TRAFFIC["burst"]["output"],
    round(TRAFFIC["burst"]["rate_per_s"] * CHECK_SECONDS)).sum())


def make_root(tmp: Path, traffic: str = "closed") -> Path:
    """A root with BENCHMARK.json, bench/configs/tiny.json and
    bench/traffic/<traffic>.json; per-layer readers and the reference
    stay the repository's."""
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    (tmp / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(tiny_config()))
    (tmp / "bench" / "traffic" / f"{traffic}.json").write_text(
        json.dumps(TRAFFIC[traffic]))
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test size"}]
    bench["workloads"] = [{"name": f"tiny.{traffic}", "config": "tiny",
                           "traffic": traffic, "chips": 1, "why": "test"}]
    for m in bench["per_layer"] + bench["end_to_end"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def tiny_cell(tmp: Path, traffic: str = "closed"):
    root = make_root(tmp, traffic)
    cell = spec.resolve(f"tiny.{traffic}", root=root,
                        bench=spec.load_benchmark(root),
                        bench_dir=root / "bench")
    cell.bench_dir = spec.BENCH
    return cell
