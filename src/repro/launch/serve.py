"""Serving driver: batched prefill + greedy decode.

Fixed-batch path (compiled prefill + decode loop, all sequences in lock-step):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-moe-30b-a3b \
      --reduced --batch 4 --prompt-len 32 --new-tokens 16

Continuous-batching engine (paged KV cache, ragged arrivals; the
``SERVE_OPTIONS`` registry derives the engine flags — ``--page-size``,
``--pool-pages``, ``--n-slots``, ``--prefill-buckets``, ``--admit-policy``):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-moe-30b-a3b \
      --reduced --engine --requests 8 --n-slots 4
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import SERVE_OPTIONS, ServeConfig
from repro.configs import get_config, get_reduced
from repro.data.pipeline import synthetic_tokens
from repro.launch.train import add_option_flags, parse_option_flags
from repro.models.transformer import init_caches, init_model
from repro.serve.decode import build_decode_step, build_prefill
from repro.sharding.plan import plan_from_mesh, single_device_plan


def serve(arch: str, *, reduced: bool = True, batch: int = 4,
          prompt_len: int = 32, new_tokens: int = 16, seed: int = 0,
          mesh=None):
    cfg = get_reduced(arch) if reduced else get_config(arch)
    if not cfg.causal:
        raise SystemExit(f"{arch} is an encoder (MLM) model; no decode step")
    plan = plan_from_mesh(mesh) if mesh is not None else single_device_plan()

    key = jax.random.PRNGKey(seed)
    params = init_model(key, cfg, plan)
    cache_len = prompt_len + new_tokens
    caches = init_caches(cfg, batch, cache_len, plan)

    rng = np.random.default_rng(seed)
    if cfg.num_codebooks > 1:
        prompts = np.stack([synthetic_tokens(rng, batch, prompt_len,
                                             cfg.vocab_size)
                            for _ in range(cfg.num_codebooks)], 1)
    else:
        prompts = synthetic_tokens(rng, batch, prompt_len, cfg.vocab_size)
    prompts = jnp.asarray(prompts)

    prefill = build_prefill(cfg, plan, params, prompts, caches, mesh=mesh)
    t0 = time.time()
    tok, caches = prefill(params, prompts, caches)
    tok.block_until_ready()
    t_prefill = time.time() - t0

    decode = build_decode_step(cfg, plan, params, tok, caches, mesh=mesh)
    out = [np.asarray(tok)]
    t0 = time.time()
    for i in range(new_tokens - 1):
        tok, caches = decode(params, tok, caches,
                             jnp.int32(prompt_len + i))
        out.append(np.asarray(tok))
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    gen = np.stack(out, axis=-1)
    print(f"prefill {prompt_len} toks x{batch}: {t_prefill*1e3:.1f} ms; "
          f"decode {new_tokens-1} steps: {t_decode*1e3:.1f} ms "
          f"({(new_tokens-1)*batch/max(t_decode,1e-9):,.0f} tok/s)")
    print("generated (first row):", gen[0].tolist())
    return gen


def serve_engine(arch: str, *, reduced: bool = True, requests: int = 8,
                 prompt_len: int = 32, new_tokens: int = 16, seed: int = 0,
                 mesh=None, serve_opts: dict | None = None):
    """Continuous-batching engine demo: ragged synthetic requests through
    the paged-KV engine, metrics printed at the end."""
    from repro.serve.engine import Engine
    cfg = get_reduced(arch) if reduced else get_config(arch)
    plan = plan_from_mesh(mesh) if mesh is not None else single_device_plan()
    scfg = dataclasses.replace(
        ServeConfig(prompt_len=prompt_len, max_new_tokens=new_tokens),
        **(serve_opts or {}))
    params = init_model(jax.random.PRNGKey(seed), cfg, plan)
    eng = Engine(params, cfg, plan, serve=scfg, mesh=mesh)

    rng = np.random.default_rng(seed)
    t0 = time.time()
    for _ in range(requests):
        plen = int(rng.integers(max(1, prompt_len // 4), prompt_len + 1))
        nt = int(rng.integers(max(1, new_tokens // 2), new_tokens + 1))
        eng.submit(synthetic_tokens(rng, 1, plen, cfg.vocab_size)[0], nt)
    out = eng.run()
    dt = time.time() - t0
    m = eng.metrics()
    n_tok = sum(len(v) for v in out.values())
    print(f"engine: {requests} requests, {n_tok} tokens in {m['ticks']} ticks"
          f" ({dt*1e3:.0f} ms, {n_tok/max(dt, 1e-9):,.0f} tok/s)")
    print(f"  pool occupancy mean/max: {m['page_occupancy_mean']:.2f}/"
          f"{m['page_occupancy_max']:.2f}  compiles: {m['compiles']}")
    print(f"  moe: drop={m['moe_drop_frac_mean']:.3f} "
          f"max_load={m['moe_hop_max_load_max']:.2f} "
          f"entropy_min={m['moe_hop_load_entropy_min']:.2f}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine (paged KV cache) "
                         "instead of the fixed-batch lock-step path")
    ap.add_argument("--requests", type=int, default=8,
                    help="engine mode: synthetic ragged requests to submit")
    add_option_flags(ap, SERVE_OPTIONS)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.engine:
        serve_engine(args.arch, reduced=args.reduced, requests=args.requests,
                     prompt_len=args.prompt_len, new_tokens=args.new_tokens,
                     seed=args.seed,
                     serve_opts=parse_option_flags(args, SERVE_OPTIONS))
    else:
        serve(args.arch, reduced=args.reduced, batch=args.batch,
              prompt_len=args.prompt_len, new_tokens=args.new_tokens,
              seed=args.seed)


if __name__ == "__main__":
    main()
