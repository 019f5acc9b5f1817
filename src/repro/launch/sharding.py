"""Sharding rules: params, batches, caches, and the MeshPar context.

Strategy (baseline — see EXPERIMENTS.md §Perf for hillclimbed variants):

* **DP**   batch over ('pod','data') — the pod axis is pure DP, so the
           inter-pod traffic is exactly one gradient all-reduce.
* **FSDP** every weight matrix also shards one dim over 'data'; XLA
           all-gathers per layer inside the scan (ZeRO-3 style) and
           reduce-scatters gradients.
* **TP**   heads / ffw / vocab / experts-hidden shard over 'model'.
* **EP/SP** expert and sequence dims shard where divisible; any dim that
           does not divide its axis stays replicated (``_fit`` guard), so
           every (arch x shape) cell lowers without manual exceptions.
"""
from __future__ import annotations

import functools
import os
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.moe import moe_mlp
from repro.models.stack import Par

from .mesh import axis_size, dp_axes


def _fit(mesh, dim_size: int, axes) -> Optional[Any]:
    """Return ``axes`` if dim_size divides the axis product, else None."""
    if axes is None:
        return None
    names = (axes,) if isinstance(axes, str) else tuple(axes)
    names = tuple(n for n in names if n in mesh.axis_names)
    if not names:
        return None
    total = axis_size(mesh, *names)
    if dim_size % total:
        return None
    return names if len(names) > 1 else names[0]


def spec_for(mesh, shape, axes_per_dim) -> P:
    """Build a PartitionSpec, dropping any entry that does not divide."""
    assert len(shape) == len(axes_per_dim)
    return P(*[_fit(mesh, s, a) for s, a in zip(shape, axes_per_dim)])


# ------------------------------------------------------------- param rules --

# rules keyed by leaf name -> axes for the *unstacked* trailing dims.
_PARAM_RULES: Dict[str, Tuple] = {
    "embed":     ("model", "data"),
    "head":      ("data", "model"),
    "wq":        ("data", "model"), "wk": ("data", "model"),
    "wv":        ("data", "model"), "wo": ("model", "data"),
    "bq":        ("model",), "bk": ("model",), "bv": ("model",),
    "wg":        ("data", "model"), "wu": ("data", "model"),
    "wd":        ("model", "data"),
    "router":    ("data", None),
    "shared_wg": ("data", "model"), "shared_wu": ("data", "model"),
    "shared_wd": ("model", "data"),
    # mamba2
    "w_in":      ("data", "model"), "w_out": ("model", "data"),
    "conv_w":    (None, "model"), "conv_b": ("model",),
    "w_B":       ("model", None), "w_C": ("model", None),
    "w_dt":      ("model", None),
    # rwkv6
    "w_r":       ("data", "model"), "w_k": ("data", "model"),
    "w_v":       ("data", "model"), "w_g": ("data", "model"),
    "w_o":       ("model", "data"),
    "w_dec_A":   ("data", None), "w_dec_B": (None, "data"),
    "w_ck":      ("data", "model"), "w_cv": ("model", "data"),
    "w_cr":      ("data", "model"),
}

_MOE_3D = {"wg", "wu", "wd"}  # under an (E, ., .) expert stack


def _leaf_spec(mesh, path: str, leaf) -> P:
    name = path.split("/")[-1]
    rule = _PARAM_RULES.get(name)
    if rule is None:
        return P()  # norms, scalars, decay vectors: replicated
    shape = leaf.shape
    rule = tuple(rule)
    # MoE expert stacks carry a leading E dim before the matrix dims
    if name in _MOE_3D and "mlp" in path and len(shape) >= 3 \
            and len(rule) + 1 <= len(shape):
        if os.environ.get("NNCG_MOE") == "ep":
            # EP-native storage: E over 'model', D over 'data' (FSDP),
            # full hidden — no per-layer reshard into the EP shard_map
            rule = ("model", "data", None) if name in ("wg", "wu") \
                else ("model", None, "data")
        else:
            rule = (None,) + rule
    # stacked group dim(s) in front
    pad = len(shape) - len(rule)
    rule = (None,) * pad + rule
    return spec_for(mesh, shape, rule)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
    return "/".join(parts)


def param_specs(mesh, params_shape_tree):
    """PartitionSpec tree congruent with the params pytree (works on
    ShapeDtypeStructs from eval_shape — no allocation)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _leaf_spec(mesh, _path_str(path), leaf),
        params_shape_tree)


def to_named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------- batches --

def batch_specs(mesh, cfg: ModelConfig, batch_shapes: Dict[str, Any]):
    dp = dp_axes(mesh)
    out = {}
    for k, sds in batch_shapes.items():
        if k == "positions3":  # (3, B, T)
            out[k] = spec_for(mesh, sds.shape, (None, dp, None))
        elif k == "embeds":    # (B, T, D)
            out[k] = spec_for(mesh, sds.shape, (dp, None, None))
        else:                  # tokens/labels/mask/positions (B, T) or (B,1)
            out[k] = spec_for(mesh, sds.shape, (dp, None))
    return out


def cache_specs(mesh, cfg: ModelConfig, cache_shape_tree):
    """KV caches: batch over dp; kv-heads over 'model' when divisible,
    else head_dim — the SAME dim the attention einsum shards, so decode
    reads/updates are collective-free. The sequence dim stays unsharded
    (dynamic_update_slice on a sharded dim forces SPMD resharding).
    SSM/RWKV states shard their head dim. Prologue caches have one fewer
    leading dim than group caches — rules are anchored at the tail."""
    dp = dp_axes(mesh)
    model_n = axis_size(mesh, "model")
    kv_on_heads = cfg.n_kv_heads and cfg.n_kv_heads % model_n == 0

    def tail_rule(name, ndim):
        if name.endswith("k") or name.endswith("v"):   # (...,B,S,Hkv,Dh)
            tail = ((dp, None, "model", None) if kv_on_heads
                    else (dp, None, None, "model"))
        elif "ssm" in name:                             # (...,B,H,N,P)
            tail = (dp, "model", None, None)
        elif "conv" in name:                            # (...,B,K-1,d_inner)
            tail = (dp, None, "model")
        elif "wkv" in name:                             # (...,B,H,N,N)
            tail = (dp, "model", None, None)
        elif "prev" in name:                            # (...,B,D)
            tail = (dp, None)
        else:
            return (None,) * ndim
        return (None,) * (ndim - len(tail)) + tail

    def leaf(path, l):
        name = _path_str(path)
        return spec_for(mesh, l.shape, tail_rule(name, l.ndim))

    return jax.tree_util.tree_map_with_path(leaf, cache_shape_tree)


# ------------------------------------------------------------------ MoE -----

def _moe_local_specs(p_tree):
    """shard_map in_specs for the expert params: TP on the hidden dim."""
    def leaf(path, l):
        name = _path_str(path).split("/")[-1]
        if name in ("wg", "wu", "shared_wg", "shared_wu"):
            return P(*([None] * (l.ndim - 1) + ["model"]))
        if name in ("wd", "shared_wd"):
            return P(*([None] * (l.ndim - 2) + ["model", None]))
        return P()
    return jax.tree_util.tree_map_with_path(leaf, p_tree)


class MeshPar(Par):
    """Parallelism context bound to a mesh: sharding constraints on the
    GSPMD path plus a shard_map'd MoE with an explicit psum schedule."""

    def __init__(self, mesh, cfg: ModelConfig, *,
                 attn_rule: Optional[str] = None):
        self.mesh = mesh
        self.cfg = cfg
        self.dp = dp_axes(mesh)
        # hillclimb knobs (EXPERIMENTS.md §Perf); env overrides for A/B
        self.attn_rule = attn_rule or os.environ.get(
            "NNCG_ATTN_RULE", "auto")

    def _c(self, x, rule):
        spec = spec_for(self.mesh, x.shape, rule)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def constraint(self, x, kind: str):
        dp, cfg = self.dp, self.cfg
        model_n = axis_size(self.mesh, "model")
        if kind == "activations":          # (B,T,D)
            # sequence parallelism: shard T over 'model' between the TP
            # regions (falls back to replicated T when T % model != 0,
            # e.g. decode T == 1) — keeps the scan carry 1/model_n sized.
            return self._c(x, (dp, "model", None))
        if kind == "logits":               # (B,T,V)
            return self._c(x, (dp, None, "model"))
        if kind == "ssm_heads":            # (B,T,H,N) rwkv/mamba heads
            return self._c(x, (dp, None, "model", None))
        if kind in ("heads", "kv_heads"):  # (B,T,H|Hkv,Dh)
            # q and kv must shard compatibly or SPMD re-shards the
            # attention einsum (involuntary remat). Priority:
            #   1. kv heads divide 'model'  -> shard heads on q and kv
            #   2. (rule 'qshard_kvrep') q heads divide -> shard q heads,
            #      replicate kv (GQA kv-replication; attention is local)
            #   3. head_dim divides -> shard Dh on both (contraction dim)
            #   4. replicate
            if cfg.n_kv_heads and cfg.n_kv_heads % model_n == 0:
                return self._c(x, (dp, None, "model", None))
            if (self.attn_rule == "qshard_kvrep" and cfg.n_heads
                    and cfg.n_heads % model_n == 0):
                if kind == "heads":
                    return self._c(x, (dp, None, "model", None))
                return self._c(x, (dp, None, None, None))
            if cfg.head_dim and cfg.head_dim % model_n == 0:
                return self._c(x, (dp, None, None, "model"))
            return self._c(x, (dp, None, None, None))
        return x

    def moe(self, x, p, cfg: ModelConfig):
        """x: (B,T,D) — kept 3-D so the shard_map in_specs mirror the
        (dp, model-SP) activation layout exactly (flattening outside the
        shard_map loses the merged-dim tiling and forces a gather)."""
        mesh, dp = self.mesh, self.dp
        model_n = axis_size(mesh, "model")
        moe_rule = os.environ.get("NNCG_MOE", "tp")
        B, T, D = x.shape
        if moe_rule == "ep" and cfg.n_experts % model_n == 0 \
                and B % axis_size(mesh, *dp) == 0 and T % model_n == 0:
            return self._moe_ep(x, p, cfg, model_n)
        in_specs = (P(dp, None, None), _moe_local_specs(p))
        out_spec = P(dp, None, None)

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                           out_specs=out_spec, check_vma=False)
        def _moe(x_local, p_local):
            b, t, d = x_local.shape
            y = moe_mlp(x_local.reshape(b * t, d), p_local,
                        top_k=cfg.top_k, act=cfg.act,
                        capacity_factor=cfg.capacity_factor)
            return jax.lax.psum(y.reshape(b, t, d), "model")

        return _moe(x, p)

    def ulysses_ok(self, cfg: ModelConfig, T: int) -> bool:
        """Ulysses sequence-parallel attention (hillclimb, §Perf):
        q heads and T must divide the model axis; kv heads either divide
        (a2a) or are small enough to all-gather (GQA kv-replication).
        Training/prefill only."""
        model_n = axis_size(self.mesh, "model")
        if not (os.environ.get("NNCG_ULYSSES") == "1" and cfg.n_heads
                and cfg.n_heads % model_n == 0 and T % model_n == 0
                and cfg.mrope_sections is None):
            return False
        if cfg.n_kv_heads % model_n == 0:
            return True
        h_loc = cfg.n_heads // model_n
        G = cfg.n_heads // cfg.n_kv_heads
        return h_loc % G == 0 or G % h_loc == 0  # group-aligned kv slice

    def ulysses_attention(self, x, p, cfg: ModelConfig, kind: str,
                          positions):
        """qkv on T-sharded activations -> all_to_all(T<->heads) ->
        full-T attention on H/model local heads -> all_to_all back.
        Wire bytes per tensor are 1/model of the Megatron-SP all-gather.
        Weights are gathered whole (FSDP gather; NOT model-sharded), so
        this trades weight residency for collective volume."""
        from repro.models.attention_vjp import flash_mha, local_mha
        from repro.models.layers import rope
        mesh, dp = self.mesh, self.dp
        B, T, D = x.shape
        H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        w_specs = jax.tree.map(lambda l: P(*([None] * l.ndim)), p)

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(dp, "model", None), w_specs, P(dp, None)),
            out_specs=P(dp, "model", None), check_vma=False)
        def _attn(x_loc, w, pos_loc):
            b, t_loc, _ = x_loc.shape

            model_n = axis_size(mesh, "model")

            def proj(name, bias, heads):
                y = jnp.einsum("btd,df->btf", x_loc,
                               w[name].astype(x_loc.dtype))
                if bias in w:
                    y = y + w[bias].astype(y.dtype)
                y = y.reshape(b, t_loc, heads, Dh)
                if heads % model_n == 0:
                    # T-shard -> head-shard (full T locally)
                    return jax.lax.all_to_all(y, "model", split_axis=2,
                                              concat_axis=1, tiled=True)
                # GQA kv-replication: gather the (small) kv over T, then
                # keep only the kv group(s) of this device's q heads
                y = jax.lax.all_gather(y, "model", axis=1, tiled=True)
                h_loc = H // model_n
                G = H // Hkv
                n_kv_loc = max(h_loc // G, 1)
                start = (jax.lax.axis_index("model") * h_loc) // G
                return jax.lax.dynamic_slice_in_dim(y, start, n_kv_loc, 2)

            q = proj("wq", "bq", H)
            k = proj("wk", "bk", Hkv)
            v = proj("wv", "bv", Hkv)
            q = rope(q, pos_loc, cfg.rope_theta, cfg.rope_dim)
            k = rope(k, pos_loc, cfg.rope_theta, cfg.rope_dim)
            if kind == "L" and cfg.window is not None:
                o = local_mha(q, k, v, cfg.window)
            else:
                o = flash_mha(q, k, v, cfg.causal, None)
            o = jax.lax.all_to_all(o, "model", split_axis=1,
                                   concat_axis=2, tiled=True)
            o = o.reshape(b, t_loc, H * Dh)
            return jnp.einsum("btf,fd->btd", o, w["wo"].astype(o.dtype))

        return _attn(x, p, positions)

    def _moe_ep(self, x, p, cfg: ModelConfig, model_n: int):
        """Expert-parallel MoE: tokens stay (dp, model-SP) sharded,
        experts sharded over 'model' (full hidden), all_to_all routing."""
        from repro.models.moe import moe_mlp_ep
        mesh, dp = self.mesh, self.dp

        def pspec(path, l):
            name = _path_str(path).split("/")[-1]
            if name in ("wg", "wu", "wd"):
                lead = (None,) * (l.ndim - 3)
                return P(*lead, "model", None, None)   # shard E
            return P(*([None] * l.ndim))               # router/shared: repl
        p_specs = jax.tree_util.tree_map_with_path(pspec, p)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P(dp, "model", None), p_specs),
                           out_specs=P(dp, "model", None), check_vma=False)
        def _moe(x_local, p_local):
            b, t, d = x_local.shape
            y = moe_mlp_ep(x_local.reshape(b * t, d), p_local,
                           top_k=cfg.top_k, n_devices=model_n,
                           axis_name="model", act=cfg.act,
                           capacity_factor=cfg.capacity_factor)
            return y.reshape(b, t, d)

        return _moe(x, p)
