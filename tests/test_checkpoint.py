"""Checkpoint manager: versioned saves, atomic LATEST, watermark GC,
bf16 round-trip, elastic reshard restore."""
import tempfile
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager


def _state(x=1.0):
    return {"params": {"w": jnp.full((8, 8), x, jnp.bfloat16),
                       "scale": jnp.full((8,), x, jnp.float32)},
            "opt": {"m": {"w": jnp.zeros((8, 8), jnp.float32)}}}


def test_save_restore_roundtrip():
    with tempfile.TemporaryDirectory() as d:
        m = CheckpointManager(d, async_save=False)
        m.save(3, _state(2.5), extra={"note": "x"})
        step, state, extra = m.restore()
        assert step == 3 and extra["note"] == "x"
        assert state["params"]["w"].dtype == jnp.bfloat16
        assert float(state["params"]["w"][0, 0]) == 2.5
        assert float(state["params"]["scale"][0]) == 2.5


def test_versioned_gc_keep_last():
    with tempfile.TemporaryDirectory() as d:
        m = CheckpointManager(d, keep_last=2, async_save=False)
        for s in (1, 2, 3, 4):
            m.save(s, _state(float(s)))
        assert m.all_steps() == [3, 4]
        step, state, _ = m.restore()
        assert step == 4 and float(state["params"]["w"][0, 0]) == 4.0
        # older pinned version still readable (readers never blocked)
        step3, state3, _ = m.restore(step=3)
        assert float(state3["params"]["w"][0, 0]) == 3.0


def test_async_save_never_blocks_then_visible():
    with tempfile.TemporaryDirectory() as d:
        m = CheckpointManager(d, async_save=True)
        m.save(1, _state(1.0))
        m.wait()
        assert m.latest_step() == 1


def test_latest_pointer_atomic():
    with tempfile.TemporaryDirectory() as d:
        m = CheckpointManager(d, async_save=False)
        m.save(5, _state())
        assert (Path(d) / "LATEST").read_text().strip() == "step_000000000005"


def test_elastic_reshard_restore():
    """Restore onto explicit shardings (different 'mesh')."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    with tempfile.TemporaryDirectory() as d:
        m = CheckpointManager(d, async_save=False)
        m.save(1, _state(1.5))
        from repro.runtime import make_mesh
        mesh = make_mesh((1,), ("data",))
        sh = {"params": {"w": NamedSharding(mesh, P()),
                         "scale": NamedSharding(mesh, P())},
              "opt": {"m": {"w": NamedSharding(mesh, P())}}}
        _, state, _ = m.restore(shardings=sh)
        assert float(state["params"]["w"][1, 1]) == 1.5
        assert state["params"]["w"].sharding.mesh.shape["data"] == 1
