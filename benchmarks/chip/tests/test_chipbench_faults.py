"""With the timed path broken underneath the harness (inside the model's
step programs, below where the harness records what it dispatched), a run
on the CPU comes out not correct: the check sees each fault a serving cell
can have."""
import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny

from chipbench import harness  # noqa: E402
from repro.models.factory import ModelBundle  # noqa: E402

DECODE = ModelBundle.decode_step_paged
PREFILL = ModelBundle.prefill_chunk_paged


def token_altered(self, p, c, t, ln, bt, act=None):
    """Each served token is the next id after the one the model chose."""
    logits, cache = DECODE(self, p, c, t, ln, bt, act)
    return jnp.roll(logits, 1, axis=-1), cache


def state_unchanged(self, p, c, t, ln, bt, act=None):
    """The decode step returns the cache it was given."""
    logits, _ = DECODE(self, p, c, t, ln, bt, act)
    return logits, c


def half_the_rows(self, p, c, t, ln, bt, act=None):
    """Every other row of the decode batch is left out of the cache."""
    half = jnp.arange(t.shape[0]) % 2 == 1
    return DECODE(self, p, c, t, ln, bt, act & half)


def half_the_chunk(self, p, c, t, st, bt, act=None, val=None):
    """Prefill writes only the first half of each chunk's tokens."""
    n = t.shape[1] if val is None else val
    return PREFILL(self, p, c, t, st, bt, act,
                   jnp.full((t.shape[0],), n, jnp.int32) // 2
                   if val is None else n // 2)


@pytest.mark.parametrize("name, fn", [
    ("decode_step_paged", token_altered),
    ("decode_step_paged", state_unchanged),
    ("decode_step_paged", half_the_rows),
    ("prefill_chunk_paged", half_the_chunk),
])
def test_a_broken_step_is_not_correct(name, fn, monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "check_device", tiny.cpu_device)
    monkeypatch.setattr(ModelBundle, name, fn)
    res = tiny.run(tiny.checkout(tmp_path), "tiny-mha.mix")
    c = res["compared"]
    assert res["correct"] is False, c
    assert c["max_logit_gap"]["value"] > c["max_logit_gap"]["limit"]
