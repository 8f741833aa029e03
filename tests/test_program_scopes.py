"""Device-side names of the engine's programs: every jitted phase is
named after its function (``jit_commit_phase``, not ``jit__unknown``),
the stages inside carry ``jax.named_scope`` names in their ops'
``op_name`` metadata (``commit/head|ring|spill``,
``resolve/gather|layout|kernel``), and the scopes change nothing but
that metadata: the compiled HLO with metadata stripped is identical to
the unscoped program's."""
import contextlib
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import BohmEngine
from repro.core.workloads import gen_ycsb_batch, make_ycsb
from repro.obs import LifecycleAuditor

R, T = 64, 16
COMMIT_SCOPES = ("commit/head", "commit/ring", "commit/spill")
RESOLVE_SCOPES = ("resolve/gather", "resolve/layout", "resolve/kernel")
LAYOUTS = {
    "one_shard": {},
    "four_logical_shards": {"n_shards": 4},
    "paged": {"paged": True, "page_slots": 2},
}


def _engine(**kw) -> BohmEngine:
    return BohmEngine(R, make_ycsb(payload_words=8, ops=10), ring_slots=4,
                      **kw)


def _batch():
    return gen_ycsb_batch(np.random.default_rng(0), T, R, theta=0.9,
                          mix="2rmw8r")


def _lowered(eng: BohmEngine, phase: str):
    """The phase's program lowered at the argument shapes the service
    dispatches it with."""
    b = _batch()
    ts = jnp.asarray(1, jnp.int32)
    plan = eng._plan(b, ts)
    w_data, _, _ = eng._exec(plan, b, eng.store)
    window = (ts, jnp.asarray(1 + T, jnp.int32))
    versions = eng.store.versions
    return {
        "plan_phase": lambda: eng._plan.lower(b, ts),
        "exec_phase": lambda: eng._exec.lower(plan, b, eng.store),
        "commit_phase": lambda: eng._commit.lower(
            plan, b, eng.store, w_data, ts, window, eng.pin_array()),
        "_readonly_resolve": lambda: eng._readonly.lower(
            versions, b.read_set, ts),
        "_bohm_step": lambda: eng._step.lower(eng.store, b, ts,
                                              eng.pin_array()),
        "gc_sharded": lambda: eng._gc.lower(versions, ts),
        "gc_sharded_audited": lambda: eng._gc_audit.lower(
            versions, ts, eng.pin_array()),
    }[phase]()


def _hlo(eng: BohmEngine, phase: str) -> str:
    return _lowered(eng, phase).compile().as_text()


def _op_names(hlo: str):
    return re.findall(r'op_name="([^"]*)"', hlo)


def _has_scope(names, scope: str) -> bool:
    # a scope inside vmap renders as ``vmap(commit/ring)/...``
    pat = re.compile(rf"(^|[/(]){re.escape(scope)}([/)]|$)")
    return any(pat.search(n) for n in names)


def strip_metadata(hlo: str) -> str:
    """The compiled module without what scopes may change: the module's
    name, the source-location tables, and every ``metadata={...}``."""
    lines = hlo.splitlines()
    head = re.sub(r"^HloModule \S+,", "HloModule _,", lines[0])
    body, started = [], False
    for line in lines[1:]:
        started = started or line.startswith(("%", "ENTRY"))
        if started:
            body.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    return "\n".join([head] + body)


@pytest.mark.parametrize("phase", [
    "plan_phase", "exec_phase", "commit_phase", "_readonly_resolve",
    "_bohm_step", "gc_sharded", "gc_sharded_audited"])
def test_program_named_after_its_function(phase):
    eng = _engine(auditor=LifecycleAuditor()
                  if phase == "gc_sharded_audited" else None)
    first = _hlo(eng, phase).splitlines()[0]
    assert first.startswith(f"HloModule jit_{phase},"), first


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_stage_scopes_in_op_metadata(layout):
    eng = _engine(**LAYOUTS[layout])
    commit = _op_names(_hlo(eng, "commit_phase"))
    for scope in COMMIT_SCOPES:
        assert _has_scope(commit, scope), scope
    assert all(n.startswith("jit(commit_phase)/") for n in commit
               if "/" in n)
    resolve = _op_names(_hlo(eng, "_readonly_resolve"))
    for scope in RESOLVE_SCOPES:
        assert _has_scope(resolve, scope), scope


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("phase", ["commit_phase", "_readonly_resolve"])
def test_scopes_change_only_metadata(phase, layout, monkeypatch):
    scoped = _hlo(_engine(**LAYOUTS[layout]), phase)
    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    try:
        plain = _hlo(_engine(**LAYOUTS[layout]), phase)
    finally:
        jax.clear_caches()
    assert not any(_has_scope(_op_names(plain), s)
                   for s in COMMIT_SCOPES + RESOLVE_SCOPES)
    assert strip_metadata(scoped) == strip_metadata(plain)


# the record-partitioned store over a 4-device mesh (shard_map bodies),
# in a subprocess with 4 forced host devices — repo convention
_MESH_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.environ["TEST_DIR"])
import contextlib
import jax
from repro.runtime import cc_mesh
import test_program_scopes as t

def hlo(phase):
    return t._hlo(t._engine(mesh=cc_mesh(4)), phase)

commit, resolve = hlo("commit_phase"), hlo("_readonly_resolve")
for scope in t.COMMIT_SCOPES:
    assert t._has_scope(t._op_names(commit), scope), scope
for scope in t.RESOLVE_SCOPES:
    assert t._has_scope(t._op_names(resolve), scope), scope
jax.clear_caches()
jax.named_scope = lambda name: contextlib.nullcontext()
assert t.strip_metadata(commit) == t.strip_metadata(hlo("commit_phase"))
assert t.strip_metadata(resolve) == t.strip_metadata(
    hlo("_readonly_resolve"))
print("MESH_SCOPES_OK")
"""


def test_stage_scopes_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TEST_DIR=os.path.dirname(os.path.abspath(__file__)))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "MESH_SCOPES_OK" in out.stdout, out.stderr[-3000:]
