"""Serving entrypoint: CAMP-quantized batched generation.

CPU-scale e2e (runs in this container):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --qmode w8a8 --batch 4 --prompt-len 32 --steps 16

Tensor-parallel serving (8 virtual devices, model axis 4):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --qmode w8a8 --tp 4 --batch 4 --prompt-len 32 --steps 16

With ``--tp``, the weights are resident model-sharded (column-parallel
q/kv/up/gate, row-parallel wo/down via the serve rule table), the paged KV
pool is head-sharded, and the engine runs every step under the serve-mode
mesh context. ``--tp-int8-reduce`` compresses the row-parallel all-reduces
to int8 on the wire.

Speculative decoding (draft–verify over the paged int8 cache):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --qmode w8a8 --batch 1 --steps 32 --spec-method ngram --spec-gamma 4

``--spec-method draft`` drives a small draft LM (``--spec-draft-config``,
e.g. ``qwen2-0.5b`` drafting for ``qwen2-72b``) over its own paged pool;
``--spec-gamma auto`` picks the window from the measured acceptance rate
through the autotune cache's ``spec|`` keys. The γ+1-row verify GEMM
shapes are pre-tuned alongside the decode/prefill shapes.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core import autotune
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.models import init_params, quantize_params
from repro.models.transformer import init_layer, init_top
from repro.parallel.sharding import (effective_model_shards, make_rules,
                                     params_pspecs)
from repro.serving.engine import generate, warm_gemm_autotune
from repro.serving.spec_decode import SpecConfig


def _serve_shardings(tree, mesh):
    """NamedSharding pytree of a (shape) tree under the serve-rule table.

    QuantizedTensor leaves place their int payload and (1, N) scale
    separately (column-consistent specs from ``params_pspecs``).
    """
    specs = params_pspecs(tree, make_rules("serve"), mesh)
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                  is_leaf=lambda x: isinstance(x, P))


def shard_params(params, mesh):
    """device_put an existing params tree to its serve-rule shardings."""
    return jax.device_put(params, _serve_shardings(params, mesh))


def init_serving_params(key, cfg, qmode: str = "none", mesh=None):
    """``init_params`` → ``quantize_params`` → placement, one layer at a time.

    Every piece (embedding, final norm and head; then each block) is drawn,
    quantized and placed in one jitted program whose outputs carry their
    serve-rule shardings on ``mesh`` (one device without a mesh). No device
    ever holds more than one block's float weights, so a model that only
    fits across the mesh in its quantized form loads there: qwen2-72b at
    w4a8 is ~35 GB of int4 weights, ~9 GB per chip at tp=4, against ~140 GB
    for the bf16 tree. The draws are ``init_params``' own (same keys, same
    ``init_top`` / ``init_layer``).
    """
    keys = jax.random.split(key, cfg.n_layers + 3)

    def placed(build, example):
        fn = jax.jit(lambda k: quantize_params(build(k), cfg, qmode))
        if mesh is None:
            return fn
        out = jax.eval_shape(fn, example)
        return jax.jit(fn, out_shardings=_serve_shardings(out, mesh))

    params = placed(lambda k: init_top(k, cfg), keys)(keys)
    fns = {}
    layers = []
    for i in range(cfg.n_layers):
        kind = (cfg.mixer_of(i), cfg.ffn_of(i))
        if kind not in fns:            # one compile per block kind
            fns[kind] = placed(lambda k, kind=kind: init_layer(k, cfg, *kind),
                               keys[i + 2])
        layers.append(fns[kind](keys[i + 2]))
    params["layers"] = layers
    return params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--qmode", default="w8a8",
                    choices=["none", "w8a8", "w4a8", "w4a4", "w8a16", "w4a16"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--sample", default="greedy", choices=["greedy", "temperature"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1,
                    help="model-axis (tensor-parallel) degree; 1 = off")
    ap.add_argument("--tp-int8-reduce", action="store_true",
                    help="int8-compress the row-parallel all-reduces")
    ap.add_argument("--spec-method", default="off",
                    choices=["off", "ngram", "draft"],
                    help="speculative decoding: model-free n-gram lookup "
                         "or a small draft model")
    ap.add_argument("--spec-gamma", default="4",
                    help="speculation window (draft tokens/step), or 'auto' "
                         "to pick from the measured acceptance rate")
    ap.add_argument("--spec-draft-config", default="qwen2-0.5b",
                    help="draft model arch for --spec-method draft "
                         "(always built with --reduced shapes)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced, qmode=args.qmode)
    key = jax.random.PRNGKey(args.seed)
    mesh = make_serving_mesh(args.tp) if args.tp > 1 else None
    t0 = time.time()
    params = init_serving_params(key, cfg, args.qmode, mesh)
    jax.block_until_ready(params)
    print(f"[serve] weights ({args.qmode}) built and placed in "
          f"{time.time()-t0:.2f}s")

    spec = None
    if args.spec_method != "off":
        gamma = args.spec_gamma if args.spec_gamma == "auto" \
            else int(args.spec_gamma)
        draft_cfg = draft_params = None
        if args.spec_method == "draft":
            draft_cfg = get_config(args.spec_draft_config, reduced=True,
                                   qmode=args.qmode)
            draft_params = init_params(jax.random.fold_in(key, 1), draft_cfg)
            if args.qmode != "none":
                draft_params = quantize_params(draft_params, draft_cfg,
                                               args.qmode)
        spec = SpecConfig(method=args.spec_method, gamma=gamma,
                          draft_cfg=draft_cfg, draft_params=draft_params)
        # pre-tune the γ+1-row verify panels next to the decode shapes
        gammas = autotune.SPEC_GAMMAS if gamma == "auto" else (gamma,)
        warm_gemm_autotune(cfg, batch_sizes=(1, args.batch),
                           tp=args.tp, spec_gammas=gammas)
        print(f"[serve] speculative decoding: {args.spec_method}, "
              f"gamma={gamma}")

    if mesh is not None:
        tp_eff = effective_model_shards(mesh, cfg.n_kv_heads)
        sharded = tp_eff if tp_eff > 1 else "replicated"
        print(f"[serve] mesh {dict(mesh.shape)}; kv-head sharding: {sharded}")

    if cfg.embedding_inputs:
        prompt = jax.random.normal(
            key, (args.batch, args.prompt_len, cfg.d_model), jnp.bfloat16)
    else:
        prompt = jax.random.randint(
            key, (args.batch, args.prompt_len), 0, cfg.vocab_size)

    t0 = time.time()
    if spec is None:
        toks = generate(params, cfg, prompt, steps=args.steps, key=key,
                        sample=args.sample, kv_dtype="int8", mesh=mesh,
                        tp_int8_reduce=args.tp_int8_reduce)
    else:
        # drive the engine directly so the acceptance stats are reportable
        from repro.serving.engine import ContinuousBatchingEngine
        from repro.serving.kv_cache import round_up
        eng = ContinuousBatchingEngine(
            params, cfg, kv_dtype="int8",
            capacity_tokens=args.batch * round_up(
                args.prompt_len + args.steps, 128),
            sample=args.sample, key=key, mesh=mesh,
            tp_int8_reduce=args.tp_int8_reduce, spec=spec)
        sids = [eng.submit(prompt[i], args.steps)
                for i in range(args.batch)]
        outs = eng.run()
        toks = jnp.asarray([outs[s] for s in sids], jnp.int32)
        s = eng.spec_summary()
        print(f"[serve] spec: {s['spec_steps']} verify steps, acceptance "
              f"{s['acceptance_rate']:.2f}, "
              f"{s['mean_tokens_per_step']:.2f} tokens/step "
              f"(gamma={s['gamma']})")
    dt = time.time() - t0
    n_new = toks.shape[0] * toks.shape[1]
    print(f"[serve] generated {toks.shape} in {dt:.2f}s "
          f"({n_new/dt:.1f} tok/s incl. compile)")
    print(f"[serve] sample row: {toks[0][:16].tolist()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
