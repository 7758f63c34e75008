import os
import sys

# tests must see 1 CPU device (the dry-run is the only 512-device user)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

from repro.configs.base import get_config  # noqa: E402


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


def reduced_f32(name: str, **overrides):
    cfg = dataclasses.replace(get_config(name).reduced(), dtype="float32")
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def mid_stream_eos(outputs) -> int:
    """A token some request emits mid-stream, at its first occurrence:
    used as EOS it stops that request early whatever numbers the
    installed jax draws for the model (the stream's own tokens choose
    it, not a pinned seed)."""
    for out in outputs:
        for j in range(1, len(out) - 1):
            if out[j] not in out[:j]:
                return out[j]
    raise AssertionError("no request emitted 3 or more tokens")
