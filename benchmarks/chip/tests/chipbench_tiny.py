"""A tiny checkout for CPU tests of the harness: reduced stablelm widths, a
few short requests, the real metric readers. Runs skip the look for a chip:
the CPU stands in for the device, with the v5e's peaks."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: reduced stablelm-3b (MHA) and stablelm-12b (GQA, 4 heads over 2)
CONFIGS = {
    "tiny-mha": {"num_layers": 2, "num_heads": 4, "num_kv_heads": 4},
    "tiny-gqa": {"num_layers": 2, "num_heads": 4, "num_kv_heads": 2},
}
#: logits of the tiny models differ from the float32 reference by bf16
#: rounding only; a served token that is not the reference's best by more
#: than this is wrong
TINY_GAP = 0.02

CHAT = {"name": "chat", "loop": "open", "rate_per_s": 6.0,
        "prompt": {"median": 8, "sigma": 0.4, "min": 4, "max": 14},
        "output": {"median": 5, "sigma": 0.4, "min": 3, "max": 8},
        "deadline_after_s": 1.0,
        "slo": {"ttft_s": 1.0, "mean_itl_s": 0.25}}
AGENT = {"name": "research", "loop": "closed", "clients": 1, "calls": 3,
         "prompt": {"median": 20, "sigma": 0.3, "min": 12, "max": 30},
         "output": {"median": 5, "sigma": 0.3, "min": 3, "max": 8}}
ENGINE = {"slots": 4, "max_seq": 64, "policy": "chunked"}


def config(name: str) -> dict:
    c = {"name": name, "family": "dense", "block": "dense", "d_model": 64,
         "head_dim": 16, "d_ff": 128, "vocab_size": 250,
         "vocab_pad_multiple": 16,
         "rope_theta": 10000.0, "norm_eps": 1e-05, "tie_embeddings": False,
         "source": "test", "reduced": [],
         "check": {"max_logit_gap": TINY_GAP}}
    c.update(CONFIGS[name])
    return c


def e2e(name, unit, better, cells=None):
    m = {"name": name, "unit": unit, "better": better, "bound": 0.25,
         "source": "host_clock"}
    if cells:
        m["workloads"] = cells
    return m


def checkout(tmp: pathlib.Path, streams=(CHAT, AGENT)) -> pathlib.Path:
    """Writes ``BENCHMARK.json`` and ``bench/`` (configs, a traffic mix
    ``mix``, the real blocks and metric readers) under ``tmp``; one cell
    per tiny config, ``<config>.mix``."""
    bench = tmp / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    for d in ("blocks", "metrics"):
        shutil.copytree(BENCH / d, bench / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for name in CONFIGS:
        (bench / "configs" / f"{name}.json").write_text(
            json.dumps(config(name)))
    (bench / "traffic" / "mix.json").write_text(
        json.dumps({"engine": ENGINE, "streams": list(streams)}))
    cells = [f"{n}.mix" for n in CONFIGS]
    doc = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": n, "source": "test",
                     "file": f"bench/configs/{n}.json", "reduced": [],
                     "why": "test"} for n in CONFIGS],
        "workloads": [{"name": f"{n}.mix", "config": n, "traffic": "mix",
                       "chips": 1, "why": "test"} for n in CONFIGS],
        "end_to_end": [e2e("setup_s", "s", "lower"),
                       e2e("itl_p99_s", "s", "lower"),
                       e2e("tokens_per_s", "tokens/s", "higher", cells)],
        "per_layer": [
            {"name": "prefill_programs", "unit": "count", "better": "lower",
             "source": "program_counter", "layer": "set-up",
             "moves": "setup_s", "workloads": cells + ["tiny-mha.solo"]},
            {"name": "decode_rows_mean", "unit": "rows", "better": "higher",
             "source": "program_counter", "layer": "scheduler",
             "moves": "tokens_per_s", "workloads": [cells[0]]}],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp


def cpu_device(chips):
    """Stands in for ``harness.check_device`` on the CPU."""
    import jax
    from chipbench import flops
    return jax.devices()[0], flops.PEAKS["TPU v5 lite"]


def run(tmp, cell, seed=2**31 + 5, seconds=1.5, trace=False):
    from chipbench import harness
    return harness.run(cell, seed, seconds, trace, checkout=tmp,
                       bench_dir=tmp / "bench")
