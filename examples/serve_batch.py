"""Batched serving demo: prefill + decode with KV caches on the real
serving path (same code the dry-run lowers at 32k/500k scale).

Loads a smoke-scale model, prefills a batch of prompts, then decodes new
tokens autoregressively — greedy sampling, per-request lengths, and a
consistency check against the full forward pass.

Run:  PYTHONPATH=src python examples/serve_batch.py --arch qwen3-1.7b
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_smoke
from repro.launch.serve import ServePlan, make_decode_fn, make_prefill_fn
from repro.models import transformer as T
from repro.parallel.sharding import DEFAULT_RULES
from repro.runtime import init_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=24)
    args = ap.parse_args()

    cfg = get_smoke(args.arch)
    max_len = args.prompt_len + args.gen_len
    params = T.init_params(cfg, jax.random.PRNGKey(0))

    plan = ServePlan(cfg=cfg, mesh=None, rules=DEFAULT_RULES,
                     max_len=max_len, batch=args.batch)
    prefill = make_prefill_fn(plan)
    decode = make_decode_fn(plan)

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32
    )

    t0 = time.perf_counter()
    logits, cache = prefill(params, {"tokens": prompts})
    tok = jnp.argmax(logits[:, -1, : cfg.vocab_size], axis=-1)[:, None].astype(jnp.int32)
    t_prefill = time.perf_counter() - t0

    generated = [tok]
    t0 = time.perf_counter()
    for t in range(args.prompt_len, max_len - 1):
        logits, cache = decode(params, tok, cache, jnp.int32(t))
        tok = jnp.argmax(logits[:, 0, : cfg.vocab_size], axis=-1)[:, None].astype(jnp.int32)
        generated.append(tok)
    t_decode = time.perf_counter() - t0
    out = jnp.concatenate(generated, axis=1)

    n_steps = len(generated) - 1
    print(f"model {cfg.name}: batch={args.batch} prompt={args.prompt_len}")
    print(f"prefill: {t_prefill*1e3:.1f} ms   decode: {t_decode/max(n_steps,1)*1e3:.1f} ms/token")
    for b in range(args.batch):
        print(f"  req{b}: {np.asarray(out[b])[:12]} ...")

    # consistency: greedy decode must equal teacher-forced forward argmax
    full = jnp.concatenate([prompts, out[:, :1]], axis=1)
    x, _, _ = T.forward(params, {"tokens": full}, cfg, plan.ctx)
    from repro.models.layers import rms_norm, unembed

    x = rms_norm(x, params["embed"]["final_norm"], cfg.norm_eps)
    lg = unembed(params["embed"], x, cfg, plan.ctx)
    want = jnp.argmax(lg[:, -2, : cfg.vocab_size], axis=-1)
    got = out[:, 0]
    assert bool(jnp.all(want == got)), (want, got)
    print("consistency vs forward pass: OK")


if __name__ == "__main__":
    init_compile_cache()
    main()
