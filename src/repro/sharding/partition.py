"""Logical-axis sharding rules: param path + shape -> PartitionSpec.

Policy (DESIGN.md §8):

* TP: head / d_ff / expert axes shard over ``model``. When a dim does not
  divide the axis (e.g. MQA's single KV head), fall back to the next
  shardable dim (head_dim), else replicate.
* FSDP (``cfg_fsdp``): the non-TP weight dim additionally shards over
  ``data`` — required for qwen3-235b (470 GB bf16; TP-only cannot fit),
  optional elsewhere.
* ZeRO-1: optimizer moments take the param spec plus ``data`` on the
  first free divisible axis.
* Activations: batch over the DP axes (pod × data when it divides);
  decode KV caches shard kv-heads over ``model`` when divisible, else the
  *sequence* axis (flash-decoding-style distributed softmax, handled by
  GSPMD reductions).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["MeshAxes", "Partitioner", "permute_expert_params"]


@dataclass(frozen=True)
class MeshAxes:
    data: tuple[str, ...] = ("data",)       # DP axes (pod, data) multi-pod
    model: str = "model"
    fsdp: bool = False                      # shard weights over data too

    @property
    def fsdp_axis(self):
        return self.data if self.fsdp else None


def _sizes(mesh) -> dict[str, int]:
    return dict(mesh.shape)      # works for Mesh and AbstractMesh


def _div(shape, i, n) -> bool:
    return 0 <= i < len(shape) and shape[i] % n == 0 and shape[i] >= n


class Partitioner:
    def __init__(self, mesh, axes: MeshAxes):
        self.mesh = mesh
        self.axes = axes
        s = _sizes(mesh)
        self.model_n = s[axes.model]
        self.data_n = 1
        for a in axes.data:
            self.data_n *= s[a]

    # -- helpers ----------------------------------------------------------
    def _model_if(self, shape, i):
        return self.axes.model if _div(shape, i, self.model_n) else None

    def _fsdp_if(self, shape, i):
        a = self.axes.fsdp_axis
        return a if (a and _div(shape, i, self.data_n)) else None

    def _attn_proj(self, shape, d_at, h_at, dh_at, out_dim=None):
        """Shard heads over model if divisible; otherwise REPLICATE over
        model (head_dim sharding would turn every score matmul into a
        partial-sum all-reduce — measured 4 TB/device/step on MQA archs).
        Small-head archs instead shard attention *activations* over the
        model axis (ShardCtx.attn_mode). FSDP on the model-dim side."""
        spec = [None] * len(shape)
        if _div(shape, h_at, self.model_n):
            spec[h_at] = self.axes.model
        spec[d_at] = self._fsdp_if(shape, d_at)
        return P(*spec)

    # -- parameter rules ----------------------------------------------------
    def param_spec(self, path: str, shape: tuple[int, ...]) -> P:
        name = path.split("/")[-1]
        stacked = path.startswith("groups/") or "shared_lora" in path
        base = self._param_spec_base(path, name,
                                     shape[1:] if stacked else shape)
        return P(None, *base) if stacked else base

    def _param_spec_base(self, path, name, shape) -> P:
        ax = self.axes
        if name == "embed":
            return P(self._model_if(shape, 0), self._fsdp_if(shape, 1))
        if name == "head":
            return P(self._fsdp_if(shape, 0), self._model_if(shape, 1))
        if name in ("frontend", "patch_proj", "down"):
            return P(self._fsdp_if(shape, 0), self._model_if(shape, 1))
        if name == "wq":
            return self._attn_proj(shape, 0, 1, 2)
        if name in ("wk", "wv"):
            return self._attn_proj(shape, 0, 1, 2)
        if name == "wo" and len(shape) == 3:     # (H, dh, d)
            spec = [None, None, self._fsdp_if(shape, 2)]
            if _div(shape, 0, self.model_n):
                spec[0] = ax.model
            return P(*spec)
        if name == "wkv_a":                      # (d, L+rope) — small, keep fsdp
            return P(self._fsdp_if(shape, 0), None)
        if name == "wkv_b":                      # (L, H, nope+v)
            return P(None, self._model_if(shape, 1), None)
        if name == "wi" and len(shape) == 3:     # dense mlp (d, c, F)
            return P(self._fsdp_if(shape, 0), None, self._model_if(shape, 2))
        if name == "wo" and len(shape) == 2:     # dense mlp (F, d)
            return P(self._model_if(shape, 0), self._fsdp_if(shape, 1))
        if name == "router":
            return P(None, None)
        if name == "wi" and len(shape) == 4:     # experts (E, d, 2, F)
            return P(self._model_if(shape, 0), self._fsdp_if(shape, 1),
                     None, None)
        if name == "wo" and len(shape) == 3 and "moe" in path:  # (E, F, d)
            return P(self._model_if(shape, 0), None, self._fsdp_if(shape, 2))
        # mamba2
        if name in ("wz", "wx"):
            return P(self._fsdp_if(shape, 0), self._model_if(shape, 1))
        if name in ("wB", "wC"):
            return P(self._fsdp_if(shape, 0), None)
        if name == "wdt":
            return P(self._fsdp_if(shape, 0), self._model_if(shape, 1))
        if name in ("dt_bias", "A_log", "D"):
            return P(self._model_if(shape, 0))
        if name == "conv_x":
            return P(None, self._model_if(shape, 1))
        if name in ("conv_B", "conv_C"):
            return P(None, None)
        if name == "gate_norm":
            return P(self._model_if(shape, 0))
        if name == "wout":
            return P(self._model_if(shape, 0), self._fsdp_if(shape, 1))
        # zamba2 lora
        if name == "a" and "lora" in path:
            return P(None, self._fsdp_if(shape, 1), None)
        if name.startswith("b_") and "lora" in path:
            return P(None, self._model_if(shape, 1), None)
        # norms / scalars / anything else: replicated
        return P(*([None] * len(shape)))

    def param_specs(self, params_tree) -> dict:
        def walk(tree, prefix):
            if isinstance(tree, dict):
                return {k: walk(v, f"{prefix}/{k}" if prefix else k)
                        for k, v in tree.items()}
            if isinstance(tree, (list, tuple)):
                return type(tree)(walk(v, f"{prefix}/{i}")
                                  for i, v in enumerate(tree))
            return self.param_spec(prefix, tree.shape)
        return walk(params_tree, "")

    # -- optimizer state (ZeRO-1) ------------------------------------------
    def zero1_spec(self, pspec: P, shape: tuple[int, ...]) -> P:
        """Param spec + ``data`` on the first free divisible axis."""
        if self.axes.fsdp:                      # already data-sharded
            return pspec
        spec = list(pspec) + [None] * (len(shape) - len(pspec))
        for i, (cur, dim) in enumerate(zip(spec, shape)):
            if cur is None and dim % self.data_n == 0 and dim >= self.data_n:
                spec[i] = self.axes.data
                return P(*spec)
        return pspec

    # -- activations / batch -------------------------------------------------
    def dp_axes_for_batch(self, batch: int) -> tuple[str, ...]:
        """Largest prefix of the DP axes whose product divides the batch."""
        axes, prod = [], 1
        s = _sizes(self.mesh)
        for a in self.axes.data:
            if batch % (prod * s[a]) == 0:
                axes.append(a)
                prod *= s[a]
        return tuple(axes)

    def batch_spec(self, shape: tuple[int, ...]) -> P:
        dp = self.dp_axes_for_batch(shape[0])
        return P(dp if dp else None, *([None] * (len(shape) - 1)))

    def cache_spec(self, path: str, shape: tuple[int, ...]) -> P:
        """KV/state cache specs. path ends with k/v/latent/k_rope/state/..."""
        name = path.split("/")[-1]
        stacked = "/groups/" in f"/{path}" or path.startswith("groups")
        core = shape[1:] if stacked else shape
        dp = self.dp_axes_for_batch(core[0])
        dp = dp if dp else None
        if name in ("k", "v"):                   # (B, T, Hkv, dh)
            if _div(core, 2, self.model_n):
                spec = P(dp, None, self.axes.model, None)
            elif _div(core, 1, self.model_n):    # shard sequence
                spec = P(dp, self.axes.model, None, None)
            else:
                spec = P(dp, None, None, None)
        elif name == "state":                    # (B, H, P, N)
            spec = P(dp, self._model_if(core, 1), None, None)
        elif name in ("conv_x",):                # (B, K-1, d_inner)
            spec = P(dp, None, self._model_if(core, 2))
        elif name in ("conv_B", "conv_C"):
            spec = P(dp, None, None)
        elif name == "latent":                   # (B, T, L) — seq-shard
            spec = P(dp, self._model_if(core, 1), None)
        elif name == "k_rope":
            spec = P(dp, self._model_if(core, 1), None)
        else:
            spec = P(dp, *([None] * (len(core) - 1)))
        return P(None, *spec) if stacked else spec

    def cache_specs(self, cache_tree) -> dict:
        def walk(tree, prefix):
            if isinstance(tree, dict):
                return {k: walk(v, f"{prefix}/{k}" if prefix else k)
                        for k, v in tree.items()}
            if isinstance(tree, (list, tuple)):
                return type(tree)(walk(v, f"{prefix}/{i}")
                                  for i, v in enumerate(tree))
            return self.cache_spec(prefix, tree.shape)
        return walk(cache_tree, "")

    # -- conversion -----------------------------------------------------------
    def named(self, spec_tree):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# expert layout application
# ---------------------------------------------------------------------------

def permute_expert_params(params_tree, permutation):
    """Apply an expert permutation (e.g. from ``repro.autoplace``) to a
    parameter tree: every ``moe`` subtree's expert-stacked weights
    (``wi (E, d, 2, F)``, ``wo (E, F, d)``) are reordered along E and the
    router's output columns are permuted to match, so routing semantics
    are unchanged while expert *e* now lives at position
    ``permutation.index(e)``. Because the expert axis shards contiguously
    over ``model`` (``param_spec``), this reorder IS the expert->shard
    layout: experts grouped by device land on that device. Stacked
    (scan-grouped) moe params keep their leading layer dim untouched."""
    import jax.numpy as jnp
    perm = jnp.asarray(list(permutation))

    def reorder(subtree):
        out = dict(subtree)
        for k in ("wi", "wo"):
            w = subtree[k]
            e_axis = w.ndim - (3 if k == "wi" else 2) - 1  # 0, or 1 if stacked
            out[k] = jnp.take(w, perm, axis=e_axis)
        r = subtree["router"]                              # (..., d, E)
        out["router"] = jnp.take(r, perm, axis=r.ndim - 1)
        return out

    def walk(tree):
        if isinstance(tree, dict):
            return {k: reorder(v) if k == "moe" else walk(v)
                    for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v) for v in tree)
        return tree

    return walk(params_tree)
