"""Serving driver: prefill a prompt batch, then batched greedy decode with a
sharded KV/state cache (the `serve_step` the decode input-shapes lower).

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --smoke \
        --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

from repro.launch.train import use_compile_cache, use_host_devices


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--devices", type=int, default=0,
                    help="CPU only: run on N host devices")
    ap.add_argument("--model-axis", type=int, default=1)
    args = ap.parse_args()
    if args.devices:
        use_host_devices(args.devices)
    use_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import EngineConfig, get_config, get_smoke_config
    from repro.core.engine import DistributedEngine
    from repro.launch.mesh import make_local_mesh
    from repro.models import transformer as model

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    cfg = cfg.replace(dtype="float32")
    assert cfg.supports_decode(), f"{cfg.name} has no decode step"
    mesh = make_local_mesh(model=args.model_axis)
    dp = mesh.devices.shape[0]
    eng = DistributedEngine(cfg, EngineConfig(train_batch_size=dp), mesh)

    max_len = args.prompt_len + args.gen
    params = eng.init_state(seed=0).params
    with mesh:
        cache = model.init_cache(cfg, args.batch, max_len, jnp.float32)
        prompt = jax.random.randint(jax.random.PRNGKey(0),
                                    (args.batch, args.prompt_len), 0,
                                    cfg.vocab_size)
        cache_shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache)
        prefill = eng.jit_prefill(
            {"tokens": jax.ShapeDtypeStruct(prompt.shape, jnp.int32)},
            cache_shapes)
        decode = eng.jit_decode_step(cache_shapes, donate=False)

        t0 = time.time()
        last_logits, cache = prefill(params, {"tokens": prompt}, cache)
        tok = jnp.argmax(last_logits[:, -1], -1)[:, None].astype(jnp.int32)
        t_prefill = time.time() - t0

        out = [tok]
        t0 = time.time()
        for i in range(args.gen - 1):
            tok, cache = decode(params, cache, tok,
                                jnp.int32(args.prompt_len + i))
            out.append(tok)
        t_decode = time.time() - t0

    gen = np.concatenate([np.asarray(t) for t in out], axis=1)
    tps = args.batch * (args.gen - 1) / max(t_decode, 1e-9)
    print(f"[serve] arch={cfg.name} batch={args.batch} "
          f"prefill({args.prompt_len} tok)={t_prefill*1e3:.1f}ms "
          f"decode={t_decode*1e3:.1f}ms ({tps:.1f} tok/s)")
    print(f"[serve] sample generations (token ids):\n{gen[:2, :16]}")


if __name__ == "__main__":
    main()
