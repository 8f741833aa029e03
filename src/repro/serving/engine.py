"""Serving engine: execution phase of the two-phase serving architecture.

``ServeEngine`` owns the jitted steps; ALL scheduling decisions (slots,
pages, timestamps, prefix sharing, GC) were made by the BohmScheduler
before a step is dispatched — the jitted functions contain zero
coordination logic, mirroring Bohm's execution threads which "proceed
without any concern for other concurrently executing transactions".

Request state lives in a Bohm MVCC record store (``repro.core.engine`` on
the sharded version rings of ``repro.store``): every serving step commits
one update batch of per-request progress records, and point lookups
(``lookup`` — request status queries) are BATCHED through
``BohmEngine.run_readonly_batch`` — one jitted snapshot-read step
resolving every lookup against the sharded ring via the ``mvcc_resolve``
kernel, with zero bookkeeping writes. Because the store is multiversion,
a monitor can pin a snapshot and read a CONSISTENT progress view while
decode steps keep committing (paper Figs 9/10, applied to serving).

Supports the dense GQA decoder family (smollm / mistral / qwen / nemotron /
llava backbones). Attention over the paged cache uses the logical gather
view on this CPU substrate; on TPU the block-table-indirect Pallas decode
kernel is the drop-in (repro/kernels/decode_attention.py).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.engine import BohmEngine, SnapshotHandle
from repro.core.txn import Workload, make_batch
from repro.models import attention as attn_mod
from repro.models import ffn as ffn_mod
from repro.models.layers import apply_rope, rms_norm
from repro.serving import pages as pages_mod
from repro.serving.scheduler import BohmScheduler, Request, StepPlan

# request-state record payload: [seq_len, n_generated, last_token+1, status]
STATE_WORDS = 4
STATE_UNKNOWN, STATE_ACTIVE, STATE_DONE = 0, 1, 2


def make_state_workload() -> Workload:
    """One-branch workload for the request-state store: a blind put of the
    4-word progress row (reads nothing — writes never wait on reads)."""
    def put(vals, args):
        return args[None, :], jnp.zeros((), bool)

    return Workload(name="serve_state", n_read=1, n_write=1,
                    payload_words=STATE_WORDS, branches=(put,))


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, slots: int = 8,
                 page_size: int = 16, num_pages: int = 512,
                 max_pages_per_seq: int = 64, temperature: float = 0.0,
                 kv_dtype=jnp.bfloat16, max_rids: int = 1024,
                 state_shards: int = 2, registry=None, tracer=None):
        assert cfg.attention == "full" and not cfg.enc_dec and not cfg.hybrid
        self.cfg = cfg
        self.params = params
        self.temperature = temperature
        self.sched = BohmScheduler(slots=slots, num_pages=num_pages,
                                   page_size=page_size,
                                   max_pages_per_seq=max_pages_per_seq)
        self.kv = pages_mod.init_paged_kv(
            cfg.num_layers, num_pages, page_size, slots, max_pages_per_seq,
            cfg.num_kv_heads, cfg.head_dim, kv_dtype)
        # MVCC request-state store: one progress record per rid, committed
        # through the full CC->exec->commit pipeline each serving step and
        # read back via batched snapshot reads over the sharded ring.
        # registry/tracer flow into the state engine, so lookup /
        # progress_view snapshot reads show up as "engine/readonly" spans
        # next to the store's plan/exec/commit phases.
        self.max_rids = max_rids
        self.state = BohmEngine(max_rids, make_state_workload(),
                                ring_slots=4, n_shards=state_shards,
                                registry=registry, tracer=tracer)
        self.tracer = self.state.tracer
        self.metrics = self.state.metrics
        self._state_dirty: Dict[int, List[int]] = {}
        self._decode = jax.jit(functools.partial(_paged_decode_step, cfg=cfg))
        self._prefill = jax.jit(functools.partial(_paged_prefill, cfg=cfg),
                                static_argnames=("prompt_len",))
        self._logits_at = jax.jit(functools.partial(_logits_at, cfg=cfg),
                                  static_argnames=("seq_len",))
        self.steps = 0

    # ------------------------------------------------------------------
    def submit(self, rid: int, prompt: np.ndarray, max_new_tokens: int):
        if not 0 <= rid < self.max_rids:
            raise ValueError(f"rid must be in [0, {self.max_rids})")
        self.sched.submit(Request(rid=rid, prompt=np.asarray(prompt,
                                                             np.int32),
                                  max_new_tokens=max_new_tokens))

    # -- request-state store -------------------------------------------
    def _mark_state(self, req: Request, status: int) -> None:
        last = req.generated[-1] + 1 if req.generated else 0
        self._state_dirty[req.rid] = [
            len(req.prompt) + len(req.generated), len(req.generated),
            last, status]

    def _flush_state(self) -> None:
        """Commit this step's progress rows as fixed-shape update batches
        (pads for idle slots keep the jitted step monomorphic; more than
        one batch only if rows somehow exceed the slot count)."""
        if not self._state_dirty:
            return
        S = self.sched.slots
        rows = sorted(self._state_dirty.items())
        self._state_dirty.clear()
        for lo in range(0, len(rows), S):
            chunk = rows[lo:lo + S]
            writes = np.full((S, 1), -1, np.int64)
            args = np.zeros((S, STATE_WORDS), np.int64)
            for i, (rid, row) in enumerate(chunk):
                writes[i, 0] = rid
                args[i] = row
            batch = make_batch(np.full((S, 1), -1), writes, np.zeros(S),
                               args)
            self.state.run_batch(batch)

    def lookup(self, rids, ts: Optional[SnapshotHandle] = None
               ) -> Dict[str, np.ndarray]:
        """Batched point lookups of request progress, resolved in one
        ``run_readonly_batch`` snapshot-read step against the sharded
        version ring (zero bookkeeping writes). ``ts`` may be a pinned
        ``SnapshotHandle`` for a consistent historical view while decode
        steps keep committing. Returns arrays keyed by field."""
        rids = np.asarray(rids, np.int64).reshape(-1)
        if len(rids) and (rids.min() < 0 or rids.max() >= self.max_rids):
            raise ValueError(f"rids must be in [0, {self.max_rids})")
        batch = make_batch(rids[:, None], np.full((len(rids), 1), -1),
                           np.zeros(len(rids)),
                           np.zeros((len(rids), STATE_WORDS)))
        vals, found, _ = self.state.run_readonly_batch(batch, ts)
        rows = np.asarray(vals)[:, 0]                 # [N, STATE_WORDS]
        return {
            "rid": np.asarray(rids),
            "seq_len": rows[:, 0],
            "n_generated": rows[:, 1],
            "last_token": rows[:, 2] - 1,             # -1 = none yet
            "status": rows[:, 3],
            "known": np.asarray(found)[:, 0] & (rows[:, 3] != STATE_UNKNOWN),
        }

    def begin_state_snapshot(self) -> SnapshotHandle:
        """Pin a consistent progress snapshot (holds state-store GC)."""
        return self.state.begin_snapshot()

    def release_state_snapshot(self, handle: SnapshotHandle) -> None:
        self.state.release_snapshot(handle)

    def progress_view(self, ts: Optional[SnapshotHandle] = None,
                      rids=None) -> Dict[str, np.ndarray]:
        """Public monitor API: a CONSISTENT snapshot of request progress
        across every rid, resolved in one ``run_readonly_batch``
        snapshot-read step (zero bookkeeping writes, never blocks the
        decode loop). ``ts`` may be a pinned ``SnapshotHandle`` (from
        ``begin_state_snapshot``) or an explicit timestamp — a dashboard
        polls the same pin repeatedly and sees the same progress rows no
        matter how many update batches commit in between; any batch
        still in flight when the pin was taken is invisible at it. With
        ``ts=None`` the view is a fresh snapshot of everything committed
        now. Returns the ``lookup`` field arrays plus the snapshot
        timestamp the view is pinned at (``view_ts``)."""
        if rids is None:
            rids = np.arange(self.max_rids)
        view = self.lookup(rids, ts)
        if isinstance(ts, SnapshotHandle):
            view_ts = ts.ts
        elif ts is None:
            view_ts = self.state.current_ts()
        else:
            view_ts = int(ts)
        view["view_ts"] = np.asarray(view_ts)
        return view

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Continuous batching loop until all submitted requests finish."""
        next_tok: Dict[int, int] = {}
        while (self.sched.queue or self.sched.num_active) and \
                max_steps > 0:
            max_steps -= 1
            for req, shared in self.sched.admit():
                if shared is None:
                    # execution phase computes the prompt's KV into the
                    # planned placeholder pages
                    pt = jnp.asarray(self.sched.page_table[req.slot],
                                     jnp.int32)
                    self.kv, logits = self._prefill(
                        self.params, self.kv,
                        jnp.asarray(req.prompt, jnp.int32), pt,
                        jnp.int32(req.slot), prompt_len=len(req.prompt))
                else:
                    # prefix hit: KV already materialised in shared pages —
                    # reading them requires no recompute and no locks; just
                    # produce the first token from the last prompt position.
                    pt = jnp.asarray(self.sched.page_table[req.slot],
                                     jnp.int32)
                    logits = self._logits_at(self.params, self.kv,
                                             jnp.asarray(req.prompt[-1:],
                                                         jnp.int32),
                                             pt, seq_len=len(req.prompt))
                tok = int(jnp.argmax(logits[-1]))
                next_tok[req.slot] = tok
                req.generated.append(tok)
                self._mark_state(req, STATE_ACTIVE)
                # page tables changed on host; sync the device copy
                self.kv = self.kv.__class__(
                    pages=self.kv.pages,
                    page_table=jnp.asarray(self.sched.page_table,
                                           jnp.int32),
                    seq_len=jnp.asarray(self.sched.seq_len, jnp.int32))
            if not self.sched.num_active:
                continue
            plan = self.sched.plan_step(next_tok)
            if not plan.active.any():
                continue
            self.kv = self.kv.__class__(
                pages=self.kv.pages,
                page_table=jnp.asarray(self.sched.page_table, jnp.int32),
                seq_len=jnp.asarray(self.sched.seq_len, jnp.int32))
            logits, self.kv = self._decode(
                self.params, self.kv, jnp.asarray(plan.tokens),
                jnp.asarray(plan.slot_pages), jnp.asarray(plan.offsets),
                jnp.asarray(plan.positions), jnp.asarray(plan.active))
            self.steps += 1
            toks = np.asarray(jnp.argmax(logits, axis=-1))
            for s, req in enumerate(self.sched.slot_req):
                if req is None or not plan.active[s]:
                    continue
                tok = int(toks[s])
                req.generated.append(tok)
                next_tok[s] = tok
                if len(req.generated) >= req.max_new_tokens:
                    self.sched.complete(s)
                    next_tok.pop(s, None)
                    self._mark_state(req, STATE_DONE)
                else:
                    self._mark_state(req, STATE_ACTIVE)
            self._flush_state()
            self.sched.end_batch()
        return self.sched.finished


# ---------------------------------------------------------------------------
# jitted execution-phase functions
# ---------------------------------------------------------------------------
def _head(params, x, cfg):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head).astype(jnp.float32)


def _attend_paged(p, h, cfg, kv, layer, positions, active):
    """One layer of paged decode attention for all slots. h: [S, 1, D]."""
    s = h.shape[0]
    q = (h @ p["attn"]["wq"]).reshape(s, 1, cfg.num_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, p["attn"]["q_norm"], cfg.norm_eps)
    q = apply_rope(q, positions[:, None], cfg.rope_theta)
    k_all, v_all = pages_mod.gather_kv(kv, layer)     # [S, T, KvH, Dh]
    from repro.models.layers import attention_decode
    out = attention_decode(q, k_all, v_all, kv.seq_len)
    return out.reshape(s, 1, cfg.q_dim) @ p["attn"]["wo"]


def _kv_proj(p, h, cfg, positions):
    s = h.shape[0]
    k = (h @ p["attn"]["wk"]).reshape(s, -1, cfg.num_kv_heads, cfg.head_dim)
    v = (h @ p["attn"]["wv"]).reshape(s, -1, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        k = rms_norm(k, p["attn"]["k_norm"], cfg.norm_eps)
    k = apply_rope(k, positions, cfg.rope_theta)
    return k, v


def _paged_decode_step(params, kv, tokens, slot_pages, offsets, positions,
                       active, *, cfg: ModelConfig):
    """One token for every active slot against the paged cache."""
    x = jnp.take(params["embed"], tokens, axis=0)[:, None, :]   # [S, 1, D]
    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        k, v = _kv_proj(lp, h, cfg, positions[:, None])
        kv = pages_mod.append_kv(kv, i, k[:, 0], v[:, 0], slot_pages,
                                 offsets, active)
        x = x + _attend_paged(lp, h, cfg, kv, i, positions, active)
        x = x + ffn_mod.dense_fwd(
            lp["ffn"], rms_norm(x, lp["ffn_norm"], cfg.norm_eps), cfg)
    logits = _head(params, x[:, 0], cfg)
    return logits, kv


def _paged_prefill(params, kv, prompt, page_table, slot, *, prompt_len: int,
                   cfg: ModelConfig):
    """Prefill one slot's prompt, writing KV into its planned pages."""
    from repro.models.layers import flash_attention
    ps = kv.page_size
    n_pages = (prompt_len + ps - 1) // ps
    x = jnp.take(params["embed"], prompt, axis=0)[None]         # [1, L, D]
    positions = jnp.arange(prompt_len)[None]
    pad = n_pages * ps - prompt_len
    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        k, v = _kv_proj(lp, h, cfg, positions)
        q = (h @ lp["attn"]["wq"]).reshape(1, prompt_len, cfg.num_heads,
                                           cfg.head_dim)
        if cfg.qk_norm:
            q = rms_norm(q, lp["attn"]["q_norm"], cfg.norm_eps)
        q = apply_rope(q, positions, cfg.rope_theta)
        att = flash_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
        x = x + att.reshape(1, prompt_len, cfg.q_dim) @ lp["attn"]["wo"]
        x = x + ffn_mod.dense_fwd(
            lp["ffn"], rms_norm(x, lp["ffn_norm"], cfg.norm_eps), cfg)
        # scatter this layer's K/V into the planned pages
        kp = jnp.pad(k[0], ((0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v[0], ((0, pad), (0, 0), (0, 0)))
        upd = jnp.stack([kp, vp], axis=1).reshape(
            n_pages, ps, 2, cfg.num_kv_heads, cfg.head_dim)
        pids = page_table[:n_pages]
        pages = kv.pages.at[i, pids].set(upd)
        kv = kv.__class__(pages=pages, page_table=kv.page_table,
                          seq_len=kv.seq_len)
    logits = _head(params, x[0, -1:], cfg)
    return kv, logits


def _logits_at(params, kv, last_tokens, page_table, *, seq_len, cfg):
    """Logits for the last prompt position using only cached pages (prefix
    hit: no prefill recompute). Runs the stack on the single last token,
    attending over the shared pages."""
    s = 1
    x = jnp.take(params["embed"], last_tokens, axis=0)[None]    # [1, 1, D]
    pos = jnp.asarray([seq_len - 1], jnp.int32)
    kv_view = kv.__class__(pages=kv.pages,
                           page_table=page_table[None],
                           seq_len=jnp.asarray([seq_len], jnp.int32))
    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        x = x + _attend_paged(lp, h, cfg, kv_view, i, pos, jnp.array([True]))
        x = x + ffn_mod.dense_fwd(
            lp["ffn"], rms_norm(x, lp["ffn_norm"], cfg.norm_eps), cfg)
    return _head(params, x[0], cfg)
