"""Continuous batching engine: fused batched decode over a paged KV cache.

One engine tick is (at most) ONE prefill chunk plus ONE fused decode step:

* **decode** runs all ``n_slots`` sequences through a single jitted call
  compiled once — dead slots carry position ``-1`` (their KV scatter is
  dropped, their output ignored) and a ``live`` mask that the MoE layers
  consume as ``token_valid``, so a decode tick's ragged dispatch puts
  exactly the live tokens' segments on the expert wire;
* **prefill** is bucketed and chunked: a prompt is processed in
  ``prefill_buckets``-sized chunks, one chunk per tick, each bucket length
  compiled once — prefill/decode disaggregation in time, so a long prompt
  never stalls the decode tick of sequences already in flight;
* **admit/evict** run against the page pool (``serve.kvcache``):
  reservation-based admission (all ``ceil((prompt+max_new)/page)`` pages up
  front — no mid-flight OOM), pages freed the tick a request finishes, and
  freed pages reused without zeroing (the paged-attention read mask hides
  stale data).

Per-tick :class:`~repro.core.pipeline.MoEStats` load telemetry (drop
fractions, per-hop max load / load entropy) is surfaced via
:meth:`Engine.metrics` — the serving-side view of the router health signals
the training watchdog reads.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.common.config import ModelConfig, ServeConfig
from repro.core.pipeline import zero_stats
from repro.models import transformer as T
from repro.serve import kvcache as KV
from repro.serve.decode import greedy_sample
from repro.sharding.plan import MeshPlan
from repro.sharding.specs import cache_specs, param_specs


# =============================================================================
# Jittable step functions (also the static-analyzer entrypoints)
# =============================================================================

def paged_decode_step_fn(params, tok, caches, table, seq_pos, live, *,
                         cfg: ModelConfig, plan: MeshPlan):
    """One fused batched decode tick over the paged KV cache.

    tok/seq_pos/live: (B,) current input token, its position, slot liveness.
    table: (B, max_pages) int32 page table (host-owned, passed per tick).
    Returns (next_tok (B,), logits (B, V_loc) fp32, MoEStats, caches).
    Dead slots produce finite garbage tokens the scheduler ignores.
    """
    positions = jnp.where(live, seq_pos, -1)[:, None]           # (B, 1)
    caches = KV.inject_tables(caches, table)
    _, logits, stats, caches = T.forward(params, tok[:, None], cfg, plan,
                                         positions=positions, caches=caches,
                                         token_valid=live[:, None])
    caches = KV.strip_tables(caches)
    lg = logits[:, 0, :]
    return greedy_sample(lg, plan), lg, stats, caches


def paged_prefill_fn(params, tokens, caches, table_row, start, n_real, *,
                     cfg: ModelConfig, plan: MeshPlan):
    """One bucketed prefill chunk for a single sequence.

    tokens: (1, S_bucket) — prompt slice padded to the bucket length;
    table_row: (1, max_pages); start: scalar absolute position of
    ``tokens[0, 0]``; n_real: scalar count of real tokens in the chunk.
    Returns (next_tok scalar — only meaningful on the final chunk —
    MoEStats, caches).  The same function serves every chunk of a long
    prompt: earlier chunks' KV is already in the pool and the gathered
    page-table view covers it.
    """
    S = tokens.shape[1]
    t = jnp.arange(S)
    valid = t < n_real
    positions = jnp.where(valid, start + t, -1)[None, :]        # (1, S)
    caches = KV.inject_tables(caches, table_row)
    _, logits, stats, caches = T.forward(params, tokens, cfg, plan,
                                         positions=positions, caches=caches,
                                         token_valid=valid[None, :])
    caches = KV.strip_tables(caches)
    last = jnp.clip(n_real - 1, 0, S - 1)
    nxt = greedy_sample(logits[0, last][None, :], plan)[0]
    return nxt, stats, caches


def _stats_specs():
    return jax.tree.map(lambda _: P(), zero_stats())


def build_paged_decode_step(cfg: ModelConfig, plan: MeshPlan, params_like,
                            caches_like, mesh=None):
    """Jitted fused decode tick (shard_mapped when a mesh is given).  The
    page pool is replicated over dp / KV-head-sharded over tp; the tiny
    per-tick scheduler arrays (tok, table, seq_pos, live) are replicated."""
    fn = partial(paged_decode_step_fn, cfg=cfg, plan=plan)
    if mesh is None:
        return jax.jit(fn, donate_argnums=(2,))
    pspec = param_specs(params_like, cfg, plan)
    cspec = cache_specs(caches_like, cfg, plan, 1)
    tp = plan.tp_axis
    lspec = P(None, tuple(tp) if isinstance(tp, (list, tuple)) and len(tp) > 1
              else (tp[0] if isinstance(tp, (list, tuple)) and tp else tp))
    sm = jax.shard_map(fn, mesh=mesh,
                       in_specs=(pspec, P(None), cspec, P(None, None), P(None),
                             P(None)),
                       out_specs=(P(None), lspec, _stats_specs(), cspec),
                       check_vma=False)
    return jax.jit(sm, donate_argnums=(2,))


def build_paged_prefill(cfg: ModelConfig, plan: MeshPlan, params_like,
                        caches_like, mesh=None):
    fn = partial(paged_prefill_fn, cfg=cfg, plan=plan)
    if mesh is None:
        return jax.jit(fn, donate_argnums=(2,))
    pspec = param_specs(params_like, cfg, plan)
    cspec = cache_specs(caches_like, cfg, plan, 1)
    sm = jax.shard_map(fn, mesh=mesh,
                       in_specs=(pspec, P(None, None), cspec, P(None, None),
                             P(), P()),
                       out_specs=(P(), _stats_specs(), cspec), check_vma=False)
    return jax.jit(sm, donate_argnums=(2,))


# =============================================================================
# Requests + engine
# =============================================================================

@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                    # (S,) int32
    max_new_tokens: int
    generated: List[int] = dataclasses.field(default_factory=list)
    pages: List[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_first: float = 0.0                  # wall time of the first token
    t_tokens: List[float] = dataclasses.field(default_factory=list)


def derive_buckets(cache_len: int, lo: int = 16) -> Tuple[int, ...]:
    """Doubling chunk lengths up to ``cache_len`` (each compiled once)."""
    if cache_len <= lo:
        return (cache_len,)
    out, s = [], lo
    while s < cache_len:
        out.append(s)
        s *= 2
    out.append(cache_len)
    return tuple(out)


class Engine:
    """Continuous-batching serving engine over the paged KV cache."""

    def __init__(self, params, cfg: ModelConfig, plan: MeshPlan, *,
                 serve: Optional[ServeConfig] = None, mesh=None, **overrides):
        serve = serve or ServeConfig()
        if overrides:
            serve = dataclasses.replace(serve, **overrides)
        if not (cfg.causal and cfg.num_codebooks == 1
                and cfg.attention in ("full", "sliding")
                and cfg.arch_type not in ("ssm", "hybrid")):
            raise ValueError(
                "Engine supports causal single-stream GQA attention archs "
                "(full/sliding); MLA absorbed decode and SSM/RWKV recurrent "
                "state over paged pools are ROADMAP follow-ups")
        self.params, self.cfg, self.plan, self.mesh = params, cfg, plan, mesh
        self.serve = serve
        self.cache_len = serve.resolved_cache_len()
        self.page_size = serve.page_size
        self.n_slots = serve.n_slots
        pool_pages = serve.resolved_pool_pages()
        self.max_pages = KV.pages_needed(self.cache_len, self.page_size)
        self.buckets = (tuple(int(x) for x in serve.prefill_buckets.split(","))
                        if serve.prefill_buckets
                        else derive_buckets(self.cache_len))
        assert list(self.buckets) == sorted(self.buckets)

        self.alloc = KV.PageAllocator(pool_pages, self.page_size)
        self.caches = KV.init_paged_caches(cfg, pool_pages, self.page_size,
                                           plan)
        B = self.n_slots
        self._sentinel = pool_pages                   # OOB page id == unmapped
        self.table_np = np.full((B, self.max_pages), self._sentinel, np.int32)
        self._tok = np.zeros((B,), np.int32)
        self._pos = np.zeros((B,), np.int32)
        self._live = np.zeros((B,), bool)
        self.slot_req: List[Optional[Request]] = [None] * B

        self.waiting: Deque[Request] = deque()
        self.prefilling: Deque[List] = deque()        # [req, slot, start]
        self.requests: Dict[int, Request] = {}        # uid -> Request (all)
        self.finished: Dict[int, List[int]] = {}
        self._uid = 0
        self.ticks = 0
        self.occupancy: List[float] = []
        self.telemetry: List[Dict[str, float]] = []

        self._decode = build_paged_decode_step(cfg, plan, params, self.caches,
                                               mesh)
        self._prefills: Dict[int, Any] = {}           # bucket len -> jitted fn

    # ------------------------------------------------------------------ submit
    def submit(self, prompt, max_new_tokens: int = 16) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        total = len(prompt) + max_new_tokens
        if total > self.cache_len:
            raise ValueError(f"request needs {total} positions > cache_len="
                             f"{self.cache_len}")
        if KV.pages_needed(total, self.page_size) > self.alloc.pool_pages:
            raise ValueError("request can never fit the page pool")
        self._uid += 1
        req = Request(self._uid, prompt, max_new_tokens,
                      t_submit=time.monotonic())
        self.waiting.append(req)
        self.requests[self._uid] = req
        return self._uid

    # ------------------------------------------------------------------ sched
    def _pick_waiting(self) -> Request:
        if self.serve.admit_policy == "sjf":
            best = min(self.waiting, key=lambda r: (len(r.prompt), r.uid))
            self.waiting.remove(best)
            return best
        return self.waiting.popleft()

    def _admit(self) -> None:
        while self.waiting:
            free_slots = [i for i, r in enumerate(self.slot_req) if r is None]
            if not free_slots:
                return
            nxt = (min(self.waiting, key=lambda r: (len(r.prompt), r.uid))
                   if self.serve.admit_policy == "sjf" else self.waiting[0])
            total = len(nxt.prompt) + nxt.max_new_tokens
            pages = self.alloc.alloc(total)
            if pages is None:
                return                                # head-of-line waits
            req = self._pick_waiting()
            assert req is nxt
            req.pages = pages
            slot = free_slots[0]
            self.table_np[slot] = self._sentinel
            self.table_np[slot, :len(pages)] = pages
            self.slot_req[slot] = req
            self.prefilling.append([req, slot, 0])

    def _prefill_for(self, bucket: int):
        if bucket not in self._prefills:
            self._prefills[bucket] = build_paged_prefill(
                self.cfg, self.plan, self.params, self.caches, self.mesh)
        return self._prefills[bucket]

    def _record_stats(self, stats) -> None:
        s = jax.device_get(stats)
        self.telemetry.append({
            "drop_frac": float(s.drop_frac),
            "hop_max_load": float(np.max(s.hop_max_load)),
            "hop_load_entropy": float(np.min(s.hop_load_entropy)),
            "fault_events": float(np.sum(s.fault_events)),
        })

    def _prefill_tick(self) -> None:
        if not self.prefilling:
            return
        ent = self.prefilling[0]
        req, slot, start = ent
        remaining = len(req.prompt) - start
        chunk = min(remaining, self.buckets[-1])
        bucket = next(b for b in self.buckets if b >= chunk)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :chunk] = req.prompt[start:start + chunk]
        fn = self._prefill_for(bucket)
        nxt, stats, self.caches = fn(
            self.params, jnp.asarray(toks), self.caches,
            jnp.asarray(self.table_np[slot:slot + 1]),
            jnp.int32(start), jnp.int32(chunk))
        ent[2] = start + chunk
        self._record_stats(stats)
        if ent[2] >= len(req.prompt):                 # prompt done -> go live
            self.prefilling.popleft()
            tok = int(jax.device_get(nxt))
            now = time.monotonic()
            req.t_first = now
            req.t_tokens.append(now)
            req.generated.append(tok)
            self._tok[slot] = tok
            self._pos[slot] = len(req.prompt)
            self._live[slot] = True
            self._maybe_finish(slot)                  # max_new_tokens == 1

    def _maybe_finish(self, slot: int) -> None:
        req = self.slot_req[slot]
        if req is not None and len(req.generated) >= req.max_new_tokens:
            self.finished[req.uid] = req.generated
            self.alloc.free(req.pages)
            self.table_np[slot] = self._sentinel
            self._live[slot] = False
            self.slot_req[slot] = None

    def _decode_tick(self) -> None:
        if not self._live.any():
            return
        nxt, _, stats, self.caches = self._decode(
            self.params, jnp.asarray(self._tok), self.caches,
            jnp.asarray(self.table_np), jnp.asarray(self._pos),
            jnp.asarray(self._live))
        nxt = np.asarray(jax.device_get(nxt))
        self._record_stats(stats)
        now = time.monotonic()
        for i in range(self.n_slots):
            if not self._live[i]:
                continue
            req = self.slot_req[i]
            tok = int(nxt[i])
            req.generated.append(tok)
            req.t_tokens.append(now)
            self._pos[i] += 1
            self._tok[i] = tok
            self._maybe_finish(i)

    # ------------------------------------------------------------------ drive
    def step(self) -> None:
        """One engine tick: admit -> one prefill chunk -> one fused decode."""
        self.ticks += 1
        self._admit()
        self._prefill_tick()
        self._decode_tick()
        self.occupancy.append(self.alloc.occupancy)

    @property
    def busy(self) -> bool:
        return bool(self.waiting or self.prefilling or self._live.any())

    def run(self, max_ticks: int = 100_000) -> Dict[int, List[int]]:
        while self.busy:
            assert self.ticks < max_ticks, "engine failed to drain"
            self.step()
        return dict(self.finished)

    # ---------------------------------------------------------------- metrics
    def compile_counts(self) -> Dict[str, int]:
        def n(fn):
            try:
                return int(fn._cache_size())
            except Exception:
                return -1
        return {"decode": n(self._decode),
                "prefill": {b: n(f) for b, f in self._prefills.items()}}

    def metrics(self) -> Dict[str, Any]:
        occ = np.asarray(self.occupancy or [0.0])
        tel = self.telemetry or [{}]
        def agg(key, red):
            vals = [t[key] for t in tel if key in t]
            return float(red(vals)) if vals else 0.0
        return {
            "ticks": self.ticks,
            "completed": len(self.finished),
            "page_occupancy_mean": float(occ.mean()),
            "page_occupancy_max": float(occ.max()),
            "moe_drop_frac_mean": agg("drop_frac", np.mean),
            "moe_hop_max_load_max": agg("hop_max_load", np.max),
            "moe_hop_load_entropy_min": agg("hop_load_entropy", np.min),
            "moe_fault_events": agg("fault_events", np.sum),
            "compiles": self.compile_counts(),
        }
