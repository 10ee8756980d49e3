"""Kernel microbenches: Pallas vs pure-jnp oracle.

Prints ``name,us_per_call,max_abs_err`` per kernel/shape.  The kernels are
compiled on a TPU and interpreted elsewhere (``kernels/ops.py``); a timing
taken in interpret mode only validates correctness and gives a relative
sense of the launch overhead, and the result JSON records which mode ran
(``env.pallas_interpret``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import fmt_table, save_json
from repro.launch.compile_cache import configure_compile_cache
from repro.kernels import ops as K
from repro.kernels import ref as R


def _time(fn, *args, reps: int = 3) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6


def run(verbose: bool = False):
    key = jax.random.PRNGKey(0)
    rows = []
    results = {}

    # --- stage_merge ----------------------------------------------------
    for shape in [(8, 256), (3, 128, 384)]:
        k1, k2, key = jax.random.split(key, 3)
        x = jax.random.normal(k1, shape, jnp.float32)
        y = jax.random.normal(k2, shape, jnp.float32)
        got = K.stage_merge(x, y, 0.3, 0.7)
        want = R.stage_merge_ref(x, y, 0.3, 0.7)
        err = float(jnp.abs(got - want).max())
        us = _time(K.stage_merge, x, y, 0.3, 0.7)
        rows.append([f"stage_merge{shape}", f"{us:.0f}", f"{err:.2e}"])
        results[f"stage_merge{shape}"] = {"us": us, "err": err}

    # --- flash attention --------------------------------------------------
    for (b, s, hq, hkv, d), kwargs in [
            ((1, 256, 4, 2, 64), dict(causal=True)),
            ((2, 128, 4, 1, 64), dict(causal=True, window=64))]:
        ks = jax.random.split(key, 4)
        key = ks[3]
        q = jax.random.normal(ks[0], (b, s, hq, d), jnp.float32)
        kk = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
        got = K.flash_attention(q, kk, v, **kwargs)
        want = jnp.swapaxes(R.flash_attention_ref(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(kk, 1, 2),
            jnp.swapaxes(v, 1, 2), **kwargs), 1, 2)
        err = float(jnp.abs(got - want).max())
        us = _time(lambda *a: K.flash_attention(*a, **kwargs), q, kk, v)
        name = f"flash_attn(b{b},s{s},h{hq}/{hkv},w{kwargs.get('window', 0)})"
        rows.append([name, f"{us:.0f}", f"{err:.2e}"])
        results[name] = {"us": us, "err": err}

    # --- ssd scan ---------------------------------------------------------
    for b, t, h, g, p, n in [(1, 128, 4, 2, 32, 16)]:
        ks = jax.random.split(key, 5)
        key = ks[4]
        x = jax.random.normal(ks[0], (b, t, h, p), jnp.float32)
        a = -jnp.abs(jax.random.normal(ks[1], (b, t, h), jnp.float32)) * 0.1
        bm = jax.random.normal(ks[2], (b, t, g, n), jnp.float32) * 0.3
        cm = jax.random.normal(ks[3], (b, t, g, n), jnp.float32) * 0.3
        got = K.ssd_scan(x, a, bm, cm, chunk=32)
        want = jnp.swapaxes(R.ssd_scan_ref(
            jnp.swapaxes(x, 1, 2), jnp.swapaxes(a, 1, 2),
            jnp.swapaxes(bm, 1, 2), jnp.swapaxes(cm, 1, 2)), 1, 2)
        err = float(jnp.abs(got - want).max())
        us = _time(lambda *ar: K.ssd_scan(*ar, chunk=32), x, a, bm, cm)
        name = f"ssd_scan(b{b},t{t},h{h},p{p},n{n})"
        rows.append([name, f"{us:.0f}", f"{err:.2e}"])
        results[name] = {"us": us, "err": err}

    mode = "interpret" if K.interpret_default() else "compiled"
    print(f"\n== kernel microbenches (Pallas {mode} vs jnp oracle) ==")
    print(fmt_table(["kernel", "us_per_call", "max_abs_err"], rows))
    save_json("kernels.json", results)
    return results


def main() -> None:
    run()


if __name__ == "__main__":
    configure_compile_cache()
    main()
