"""Batched serving example: prefill + greedy decode across architectures.

Runs the reduced variant of three assigned families (dense / MoE / SSM)
through ``Model.prefill`` and ``Model.decode_step``, the serving path of
``repro.launch.serve``, and prints throughput.

    PYTHONPATH=src python examples/serve_batched.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.data.pipeline import SyntheticLM, batch_for
from repro.models.model import build_model

ARCHS = ["qwen3-4b", "granite-moe-3b-a800m", "mamba2-1.3b"]
BATCH, PROMPT, NEW = 4, 24, 12

for arch in ARCHS:
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    src = SyntheticLM(cfg.vocab_size, seed=7)
    rng = np.random.default_rng(0)
    batch = {k: jnp.asarray(v) for k, v in
             batch_for(cfg, src.sample(rng, BATCH, PROMPT), rng).items()}
    cap = PROMPT + NEW + (cfg.num_patches if cfg.arch_type == "vlm" else 0)

    # argmax inside the jitted steps: one dispatch per token, and the
    # generated tokens are drained once at the end
    def _prefill(p, b, model=model, cap=cap):
        logits, cache = model.prefill(p, b, cap)
        return cache, jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    def _decode(p, c, t, model=model):
        logits, cache = model.decode_step(p, c, t)
        return cache, jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    prefill = jax.jit(_prefill)
    decode = jax.jit(_decode)

    cache, tok = prefill(params, batch)
    toks = [tok]
    t0 = time.time()
    for _ in range(NEW - 1):
        cache, tok = decode(params, cache, tok)
        toks.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    gen = np.stack(jax.device_get(toks), 1)
    assert np.isfinite(gen).all() and gen.shape == (BATCH, NEW)
    print(f"{arch:22s} [{cfg.arch_type:6s}] decode "
          f"{BATCH * (NEW - 1) / dt:6.1f} tok/s (batch {BATCH})  "
          f"sample: {gen[0, :8].tolist()}")
print("ok")
