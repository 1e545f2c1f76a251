"""A tiny copy of the benchmark for CPU tests: same harness, adapters,
reference code and metric readers; every configuration of the benchmark as
a two-layer model of width 256, and short mixes."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
CONFIG = "internlm2-1.8b-tcsc30"
DENSE = "internlm2-1.8b-dense"
SIZES = {"hidden_size": 256, "intermediate_size": 512,
         "num_attention_heads": 2, "num_key_value_heads": 1,
         "num_hidden_layers": 2, "vocab_size": 512}
MIXES = {
    "decode-batch": {"clients": 4, "slots": 4, "page_size": 16,
                     "warm_s": 0.5, "block": 16,
                     "prompt_tokens": {"median": 24, "min": 16, "max": 40},
                     "output_tokens": {"median": 12, "min": 8, "max": 24}},
    "chat-rate": {"slots": 4, "page_size": 16, "rate_per_s": 4.0,
                  "warm_s": 0.5, "block": 16,
                  "prompt_tokens": {"median": 24, "min": 8, "max": 64},
                  "output_tokens": {"median": 8, "min": 4, "max": 16}},
}
# set as the cell's limits are, from readings at this size on the CPU:
# sound runs read max_gap <= 0.0147 and mean_gap <= 2.6e-4 over 12 seeds,
# the fp8 control max_gap >= 0.093 and mean_gap >= 0.0049 over 6
LIMITS = {"max_gap": 0.04, "mean_gap": 0.0015}


def make(tmp: pathlib.Path, limits: dict = LIMITS) -> pathlib.Path:
    """A checkout root under ``tmp`` with ``BENCHMARK.json`` and a
    ``perfbench`` directory of tiny files; returns the root."""
    base = tmp / "perfbench"
    for d in ("configs", "traffic", "limits"):
        (base / d).mkdir(parents=True, exist_ok=True)
    for d in ("metrics", "adapters"):
        (base / d).symlink_to(BENCH / d)
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((BENCH / "configs" / f"{c['name']}.json")
                         .read_text())
        cfg.update(SIZES)
        (base / "configs" / f"{c['name']}.json").write_text(json.dumps(cfg))
        shutil.copy(BENCH / "configs" / f"{c['name']}.py", base / "configs")
    for w in bench["workloads"]:
        mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        mix.update(MIXES[w["traffic"]])
        (base / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(mix))
        (base / "limits" / f"{w['name']}.json").write_text(
            json.dumps({"limits": limits}))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
