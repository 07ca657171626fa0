"""The persistent compilation cache keeps every program of a run inside the
checkout, even where the environment caps the cache's size: a run whose
programs exceed the cap would otherwise evict its first programs before the
next run reads them, and every run would compile everything again."""
import os
import pathlib
import subprocess
import sys

import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

#: one run: compile eight distinct programs, print the persistent cache's
#: hits and the programs it wrote
RUN = """
import os, sys, pathlib
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from repro.launch import compile_cache
compile_cache.CHECKOUT = pathlib.Path(sys.argv[1])
compile_cache.enable_compile_cache()
seen = {"hits": 0, "written": 0}
def on(event, **kw):
    if event == "/jax/compilation_cache/cache_hits":
        seen["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        seen["written"] += 1
jax.monitoring.register_event_listener(on)
for n in range(8):
    jax.jit(lambda x, n=n: jnp.sin(x) * (n + 1) + jnp.cumsum(x) ** n)(
        jnp.ones(64))
print(seen["hits"], seen["written"])
"""


def _run(checkout, cache_dir, cap):
    env = dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               JAX_COMPILATION_CACHE_MAX_SIZE=str(cap))
    out = subprocess.run([sys.executable, "-c", RUN, str(checkout)],
                         env=env, capture_output=True, text=True, timeout=300,
                         check=True).stdout.split()
    return int(out[-2]), int(out[-1])


@pytest.mark.parametrize("inside", [True, False],
                         ids=["inside_checkout", "outside_checkout"])
def test_a_second_run_reads_every_program_of_the_checkout(tmp_path, inside):
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    cache_dir = (checkout if inside else tmp_path) / ".jax_cache"
    cap = 30_000            # bytes: about six of the ten programs written
    hits, written = _run(checkout, cache_dir, cap)
    assert hits == 0 and written >= 8
    again, _ = _run(checkout, cache_dir, cap)
    if inside:
        assert again == written
    else:
        # a directory of someone else's keeps its cap, and the cap thrashes
        assert again < written
