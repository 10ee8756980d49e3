"""Batched serving driver: prefill a prompt batch, then greedy-decode.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
        --batch 4 --prompt-len 32 --new-tokens 16

Decode runs ``Model.decode_step``: one token against a KV/SSM cache, with a
sliding-window ring cache when the config or ``--window`` says so.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, PAPER_MODELS, get_config, reduced
from repro.telemetry import log
from repro.data.pipeline import SyntheticLM, batch_for
from repro.launch.compile_cache import configure_compile_cache
from repro.models.model import build_model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b",
                    choices=sorted(ARCHS) + sorted(PAPER_MODELS))
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--window", type=int, default=0,
                    help=">0: SWA ring-cache serving (long-context mode)")
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    log(f"serving {cfg.name}: {cfg.param_count() / 1e6:.1f}M params, "
          f"batch={args.batch} prompt={args.prompt_len} "
          f"new={args.new_tokens} window={args.window or 'full'}")

    src = SyntheticLM(cfg.vocab_size, seed=7)
    rng = np.random.default_rng(0)
    raw = src.sample(rng, args.batch, args.prompt_len)
    batch = {k: jnp.asarray(v)
             for k, v in batch_for(cfg, raw, rng).items()}

    capacity = args.window or (args.prompt_len + args.new_tokens +
                               (cfg.num_patches if cfg.arch_type == "vlm"
                                else 0))
    # greedy selection lives INSIDE the jitted steps: one dispatch per
    # token, logits never leave the device
    def _prefill(p, b):
        logits, cache = model.prefill(p, b, capacity)
        return cache, jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)

    def _decode(p, c, t):
        logits, cache = model.decode_step(p, c, t, window=args.window)
        return cache, jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)

    prefill = jax.jit(_prefill)
    decode = jax.jit(_decode)

    t0 = time.time()
    cache, next_tok = prefill(params, batch)
    jax.block_until_ready(next_tok)
    t_prefill = time.time() - t0

    out_tokens = [next_tok]
    t0 = time.time()
    for _ in range(args.new_tokens - 1):
        cache, next_tok = decode(params, cache, next_tok)
        out_tokens.append(next_tok)
    jax.block_until_ready(next_tok)
    t_decode = time.time() - t0

    # ONE explicit drain for the whole generation
    gen = np.stack(jax.device_get(out_tokens), axis=1)
    log(f"prefill: {t_prefill * 1e3:.0f} ms "
          f"({args.batch * args.prompt_len} tokens)")
    log(f"decode:  {t_decode * 1e3:.0f} ms "
          f"({args.batch * (args.new_tokens - 1)} tokens, "
          f"{(args.new_tokens - 1) / max(t_decode, 1e-9):.1f} tok/s/seq)")
    for i in range(min(args.batch, 2)):
        log(f"  seq{i}: prompt={raw[i, :8].tolist()}... "
              f"gen={gen[i].tolist()}")
    assert np.isfinite(gen).all()


if __name__ == "__main__":
    main()
