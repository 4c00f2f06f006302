"""Serving launcher CLI — batched decode against the production mesh.

  # single-stream batched decode (one prefill launch + greedy loop)
  python -m repro.launch.serve --arch qwen3-14b --smoke --batch 4 \
      --prompt_len 16 --gen_len 32
  python -m repro.launch.serve --arch mixtral-8x7b --mesh production \
      --cache_len 32768            # fleet mode (TPU)

  # continuous batching (DESIGN.md §16): slot-table engine driven by a
  # seeded Poisson trace at the offered QPS
  python -m repro.launch.serve --arch qwen3-14b --smoke --continuous \
      --qps 20 --slots 8 --requests 64

  # train -> serve handoff: restore params from a training checkpoint
  python -m repro.launch.serve --arch qwen3-14b --smoke --continuous \
      --ckpt_dir /tmp/run/ckpt

Prefill is ONE ``model.prefill_cache`` launch for the whole prompt
batch (the §16 flash-prefill path — the old launcher streamed the
prompt through ``prompt_len`` per-token decode steps and called that
"prefill"); compile time is reported separately so prefill tokens/s is
an honest steady-state number.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import restore_params
from repro.configs import get_config, get_smoke_config
from repro.launch import compile_cache
from repro.launch import sharding as sh
from repro.launch.mesh import make_production_mesh
from repro.models import build
from repro.models.inputs import make_train_batch
from repro.serving import Engine, EngineConfig, make_serve_step, make_trace
from repro.sharding_ctx import activation_sharding


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt_len", type=int, default=16)
    ap.add_argument("--gen_len", type=int, default=32)
    ap.add_argument("--cache_len", type=int, default=0)
    ap.add_argument("--mesh", default="none", choices=["none", "production"])
    ap.add_argument("--multi_pod", action="store_true")
    ap.add_argument("--ckpt_dir", default="",
                    help="restore params from a training checkpoint "
                         "(train->serve handoff, §15/§16 integrity rules)")
    # continuous-batching engine mode
    ap.add_argument("--continuous", action="store_true",
                    help="slot-table continuous batching (§16)")
    ap.add_argument("--qps", type=float, default=20.0,
                    help="offered load for --continuous")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    compile_cache.enable()
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    model = build(cfg)
    key = jax.random.PRNGKey(0)
    cache_len = args.cache_len or (args.prompt_len + args.gen_len)

    def load_params():
        if args.ckpt_dir:
            step, params = restore_params(args.ckpt_dir,
                                          model.param_shapes())
            print(f"restored params from {args.ckpt_dir} step {step}")
            return params
        return model.init(key)

    if args.continuous:
        if args.mesh == "production":
            raise SystemExit("--continuous runs single-host for now; "
                             "drop --mesh production")
        _serve_continuous(model, cfg, load_params(), args)
        return

    if args.mesh == "production":
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        rules = sh.param_rules(cfg, mesh)
        pshapes = model.param_shapes()
        pshard = sh.tree_shardings(mesh, model.logical_axes(), rules,
                                   pshapes)
        ctx = activation_sharding(mesh, sh.activation_rules(cfg, mesh))
        with mesh, ctx:
            params = jax.jit(model.init, out_shardings=pshard)(key)
            serve_step = jax.jit(make_serve_step(model),
                                 donate_argnums=(1,))
            _loop(model, cfg, params, cache_len, serve_step, args, key)
    else:
        params = load_params()
        serve_step = jax.jit(make_serve_step(model), donate_argnums=(1,))
        _loop(model, cfg, params, cache_len, serve_step, args, key)


def _serve_continuous(model, cfg, params, args):
    cache_len = args.cache_len or 64
    eng = Engine(model, params, EngineConfig(
        slots=args.slots, cache_len=cache_len, greedy=True,
        eos_id=0, seed=args.seed))
    trace = make_trace(args.seed, n_requests=args.requests, qps=args.qps,
                       vocab_size=cfg.vocab_size)
    res = eng.run(trace)  # wall clock: offered-load mode
    lat = res.latency_percentiles()
    print(f"arch={cfg.name} slots={args.slots} qps={args.qps} "
          f"requests={args.requests}")
    print(f"completed={len(res.completions)} "
          f"tokens={res.generated_tokens} "
          f"tok/s={res.tokens_per_s:.1f} "
          f"p50={lat['p50'] * 1e3:.1f}ms p99={lat['p99'] * 1e3:.1f}ms "
          f"decode_shapes={res.decode_step_shapes} "
          f"prefill_launches={res.n_prefill_launches}")


def _loop(model, cfg, params, cache_len, serve_step, args, key):
    B = args.batch
    batch = make_train_batch(key, cfg, B, args.prompt_len)
    prompts = batch["tokens"]
    # vlm prompts carry a patch prefix: positions (and the cache) include
    # it, so decode starts after prompt + patches
    extra = batch["patches"].shape[1] if cfg.family == "vlm" else 0
    cache_len += extra
    start = args.prompt_len + extra

    # ---- prefill: ONE launch for the whole prompt batch; compile timed
    # separately so tokens/s reflects steady-state, not tracing
    prefill = jax.jit(
        lambda p, b: model.prefill_cache(p, b, cache_len))
    t0 = time.perf_counter()
    logits, cache = prefill(params, batch)
    jax.block_until_ready(logits)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    logits, cache = prefill(params, batch)
    jax.block_until_ready(logits)
    prefill_s = time.perf_counter() - t0
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    tok = nxt.reshape(prompts[..., :1].shape)
    t0 = time.perf_counter()
    for t in range(start, start + args.gen_len):
        pos = jnp.full((B, 1), t, jnp.int32)
        _, nxt, cache = serve_step(params, cache, tok, pos)
        tok = nxt.reshape(tok.shape)
    jax.block_until_ready(tok)
    decode_s = time.perf_counter() - t0
    n_prompt = B * args.prompt_len
    print(f"arch={cfg.name} batch={B} prompt={args.prompt_len} "
          f"gen={args.gen_len}")
    print(f"prefill {prefill_s * 1e3:.1f} ms (1 launch, "
          f"{n_prompt / prefill_s:.1f} tok/s; compile "
          f"{compile_s:.2f}s) | {decode_s / args.gen_len * 1e3:.1f} "
          f"ms/step | {B * args.gen_len / decode_s:.1f} tok/s")


if __name__ == "__main__":
    main()
