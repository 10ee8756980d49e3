"""Stage partitioning — maps a model's stacked parameter pytree onto the
paper's pipeline stages.

Convention (paper §5.1): stage ``S0`` holds the embedding + deembedding (and
any heterogeneous extras: learned positions, VLM projector, zamba2's shared
attention block, whisper's encoder-side norms...).  Transformer stages
``S1..SK`` each hold ``num_layers / K`` consecutive blocks.  Because blocks
are stacked on axis 0, a stage is a contiguous slice of every leaf of the
tower subtree — so the CheckFree merge is a pair of slices + an axpy, which
is exactly what the ``stage_merge`` Pallas kernel implements on TPU.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig

Params = Dict[str, Any]


def balanced_layer_counts(num_layers: int, num_stages: int) -> Tuple[int, ...]:
    """Most-even contiguous split of ``num_layers`` over ``num_stages``.

    The first ``num_layers % num_stages`` stages take one extra layer, so
    any two stages differ by at most one layer — the layout elastic
    repartitioning rebalances to after a shrink or grow.
    """
    assert 1 <= num_stages <= num_layers, (num_layers, num_stages)
    base, extra = divmod(num_layers, num_stages)
    return tuple(base + (1 if i < extra else 0) for i in range(num_stages))


def towers(cfg: ModelConfig) -> List[Tuple[str, int]]:
    """The staged residual towers of each family: (param key, num layers)."""
    if cfg.arch_type in ("dense", "moe", "vlm"):
        return [("blocks", cfg.num_layers)]
    if cfg.arch_type in ("ssm", "hybrid"):
        return [("mamba" if cfg.arch_type == "hybrid" else "blocks",
                 cfg.num_layers)]
    if cfg.arch_type == "encdec":
        return [("enc_blocks", cfg.num_encoder_layers),
                ("dec_blocks", cfg.num_layers)]
    raise ValueError(cfg.arch_type)


class StagePartition:
    """Contiguous partition of the primary tower into ``num_stages`` stages.

    The default layout is equal-size; ``layer_counts`` gives each stage a
    variable number of consecutive blocks (elastic repartitioning after a
    permanent node departure shrinks K stages to K-1 by re-cutting the same
    tower).  All bounds are static Python ints, so every layout compiles to
    its own XLA program — the fused hot path never traces a dynamic shape.

    For encdec archs the partition applies to the decoder tower (the encoder
    is partitioned separately with the same mechanics via a second instance).
    """

    def __init__(self, cfg: ModelConfig, num_stages: int, tower: int = 0,
                 layer_counts: Optional[Sequence[int]] = None):
        self.cfg = cfg
        self.tower_key, self.num_layers = towers(cfg)[tower]
        self.num_stages = num_stages
        if layer_counts is None:
            layer_counts = balanced_layer_counts(self.num_layers, num_stages)
        self.layer_counts = tuple(int(c) for c in layer_counts)
        assert len(self.layer_counts) == num_stages, (
            f"{len(self.layer_counts)} counts for {num_stages} stages")
        assert all(c >= 1 for c in self.layer_counts), self.layer_counts
        assert sum(self.layer_counts) == self.num_layers, (
            f"{self.layer_counts} does not cover {self.num_layers} layers")
        offsets = [0]
        for c in self.layer_counts:
            offsets.append(offsets[-1] + c)
        self._offsets = tuple(offsets)
        self.uniform = len(set(self.layer_counts)) == 1
        #: layers per stage for the uniform layout, None when variable
        self.layers_per_stage = self.layer_counts[0] if self.uniform else None

    # ---- slicing -----------------------------------------------------
    def stage_bounds(self, i: int) -> Tuple[int, int]:
        assert 0 <= i < self.num_stages
        return self._offsets[i], self._offsets[i + 1]

    def stage_of_layer(self, layer: int) -> int:
        """The stage whose contiguous range holds ``layer``."""
        assert 0 <= layer < self.num_layers
        for i in range(self.num_stages):
            if layer < self._offsets[i + 1]:
                return i
        raise AssertionError(layer)

    def get_stage(self, params: Params, i: int) -> Params:
        lo, hi = self.stage_bounds(i)
        return jax.tree.map(lambda a: a[lo:hi], params[self.tower_key])

    def set_stage(self, params: Params, i: int, stage: Params) -> Params:
        lo, _ = self.stage_bounds(i)
        new_tower = jax.tree.map(
            lambda a, s: jax.lax.dynamic_update_slice_in_dim(
                a, s.astype(a.dtype), lo, axis=0),
            params[self.tower_key], stage)
        out = dict(params)
        out[self.tower_key] = new_tower
        return out

    # ---- per-stage gradient norms (Alg. 1's omega) ---------------------
    @jax.named_scope("stage_omegas")
    def stage_grad_sqnorms(self, grads: Params) -> jnp.ndarray:
        """omega_i = ||grad W_{s,i}||^2, a (num_stages,) vector.

        Computed from the stacked tower: per-layer squared norms then a
        segment-sum into stages.  O(|params|) reads, negligible extra memory —
        matching the paper's claim that tracking omega is ~free.
        """
        per_layer = jnp.zeros((self.num_layers,), jnp.float32)
        for leaf in jax.tree.leaves(grads[self.tower_key]):
            sq = jnp.square(leaf.astype(jnp.float32))
            per_layer = per_layer + jnp.sum(
                sq.reshape(leaf.shape[0], -1), axis=1)
        if self.uniform:
            # keep the seed reduction shape on the uniform layout so fused
            # traces stay bit-identical with pre-elastic runs
            return jnp.sum(per_layer.reshape(self.num_stages,
                                             self.layers_per_stage), axis=1)
        return jnp.stack([jnp.sum(per_layer[lo:hi])
                          for lo, hi in zip(self._offsets[:-1],
                                            self._offsets[1:])])

    # ---- replicated (stage-0) leaves ----------------------------------
    def stage0_keys(self, params: Params) -> List[str]:
        """Keys that belong to the embedding stage / replication path."""
        return [k for k in params.keys() if k not in
                {key for key, _ in towers(self.cfg)}]


# ---------------------------------------------------------------------------
# elastic re-layout helpers
# ---------------------------------------------------------------------------

def remap_stage_stats(old: StagePartition, new: StagePartition,
                      values: Any) -> Any:
    """Re-bucket per-stage statistics (omegas) from ``old`` to ``new``.

    Each old stage's value is spread uniformly over its layers, then the
    per-layer values are re-summed under the new bounds — the natural
    re-layout of an additive per-stage quantity like ``||grad W_i||^2``.
    Returns None when ``values`` is None (no omegas tracked yet).
    """
    if values is None:
        return None
    assert old.num_layers == new.num_layers, (old.num_layers, new.num_layers)
    vals = jnp.asarray(values, jnp.float32)
    per_layer = jnp.concatenate([
        jnp.full((old.layer_counts[i],), vals[i] / old.layer_counts[i])
        for i in range(old.num_stages)])
    return jnp.stack([jnp.sum(per_layer[lo:hi])
                      for lo, hi in zip(new._offsets[:-1], new._offsets[1:])])


def moved_layers(old: StagePartition, old_slots: Sequence[int],
                 new: StagePartition, new_slots: Sequence[int]) -> int:
    """How many layers change owning *node* between two layouts.

    ``old_slots``/``new_slots`` map partition stage index -> cluster slot;
    a layer moves when the slot that owns it differs, which is what the
    re-layout pricing (bytes over the link bandwidth) charges for.
    """
    assert old.num_layers == new.num_layers
    assert len(old_slots) == old.num_stages
    assert len(new_slots) == new.num_stages
    n = 0
    for layer in range(old.num_layers):
        a = old_slots[old.stage_of_layer(layer)]
        b = new_slots[new.stage_of_layer(layer)]
        n += a != b
    return n
