#!/usr/bin/env python3
"""The Pallas kernels keep their names under a tensor-parallel mesh.

    JAX_PLATFORMS=cpu python3 bench/tests/kernel_names_tp.py

Compiles, for a described TPU v5e 2x2 (no chip needed), the decode forward
and a prefill forward of a small w4a8 Qwen2 (2 layers, d 2048, 16 heads of
128, 8 kv heads, d_ff 4096, untied vocabulary 8192) at degree 4, under the
serve rules and with the kernels' TPU branch taken, and checks that the
compiled programs name their Pallas custom calls ``camp_gemm_fused_w4a8.<n>``,
``_paged_attention_pallas.<n>`` and ``_paged_prefill_pallas.<n>``: the
kernel gate and the rooflines find the kernels by those names. Prints the
names and the collectives found, one JSON line; exits 1 where a name is
missing.

``test_bench_tp.py`` runs it in a process of its own, with
``ALLOW_MULTIPLE_LIBTPU_LOAD=1``: it loads the TPU compiler library, which
``tests/test_tpu_compile.py`` may hold in another test worker, and forces
the kernels' TPU branch for the whole process.
"""
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
WANT = ("camp_gemm_fused_w4a8", "_paged_attention_pallas",
        "_paged_prefill_pallas")
NP, PS, B, MAXP, CHUNK = 64, 128, 8, 8, 256


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.kernels import backend
    from repro.launch.serve import _serve_shardings, init_serving_params
    from repro.models.transformer import forward
    from repro.parallel.sharding import make_rules, mesh_context
    from repro.serving.kv_cache import PagedDecodeCache, PagedPrefillCache

    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    backend.on_tpu = lambda: True        # the kernels' compiled TPU branch
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config("qwen2-72b", n_layers=2, d_model=2048, n_heads=16,
                     n_kv_heads=8, head_dim=128, d_ff=4096, vocab_size=8192,
                     qmode="w4a8")
    shapes = jax.eval_shape(lambda k: init_serving_params(k, cfg, "w4a8"),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, _serve_shardings(shapes, mesh))

    def arr(shape, dt, spec=P()):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    def pool():
        kv = (NP, cfg.n_kv_heads, PS)
        return (arr(kv + (cfg.hd,), jnp.int8, P(None, "model", None, None)),
                arr(kv + (cfg.hd,), jnp.int8, P(None, "model", None, None)),
                arr(kv, jnp.float32, P(None, "model", None)),
                arr(kv, jnp.float32, P(None, "model", None)))

    def step(**kw):
        return jax.jit(lambda p, t, pos, c: forward(
            p, cfg, t, positions=pos, caches=c, **kw))

    with mesh_context(mesh, make_rules("serve"), mode="serve"):
        decode = step().lower(
            params, arr((B, 1), jnp.int32), arr((B, 1), jnp.int32),
            [{"attn": PagedDecodeCache(*pool(), arr((B, MAXP), jnp.int32),
                                       arr((B,), jnp.int32))}
             for _ in range(cfg.n_layers)]).compile().as_text()
        prefill = step(last_logits_only=True).lower(
            params, arr((1, CHUNK), jnp.int32), arr((1, CHUNK), jnp.int32),
            [{"attn": PagedPrefillCache(*pool(), arr((MAXP,), jnp.int32),
                                        q_start=CHUNK)}
             for _ in range(cfg.n_layers)]).compile().as_text()
    out = {}
    for name, txt in (("decode", decode), ("prefill", prefill)):
        calls = re.findall(
            r"%?([\w.]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", txt)
        out[name] = {"kernels": sorted({c.rsplit(".", 1)[0] for c in calls}),
                     "calls": len(calls),
                     "collectives": sorted(set(re.findall(
                         r"\b(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)\(", txt)))}
    found = set(out["decode"]["kernels"]) | set(out["prefill"]["kernels"])
    out["missing"] = [k for k in WANT if k not in found]
    print(json.dumps(out))
    return 1 if out["missing"] else 0


if __name__ == "__main__":
    sys.exit(main())
