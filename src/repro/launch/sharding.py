"""GSPMD sharding rules for every architecture (pjit / NamedSharding).

Rules are name-based over parameter pytree paths, then left-padded with
``None`` to the leaf rank so the same table covers both unrolled (per-layer
dict) and lax.scan-stacked ((L, ...) leading dim) layouts:

  * tensor parallel over ``model``: attention heads (wq/wk/wv col, wo row),
    FFN d_ff (w_in col, w_out row), MoE experts (w_up/w_down dim0),
    MLA per-head factors (w_uk/w_uv dim0), vocab (embedding rows / lm_head
    cols), SSM/RG-LRU channel dims;
  * data parallel over ``pod``x``data``: the batch dim of every activation;
  * optional FSDP: weights additionally sharded over ``data`` on their first
    free dim (used for the biggest train configs, and mirrored onto the
    optimizer state).

Caches: batch over ``pod``x``data`` and kv-heads/channels over ``model``;
for long_500k (batch=1) the cache *sequence* dim is sharded over ``data``
instead — sequence parallelism for the KV cache.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# name -> base spec for the *trailing* dims of the leaf
_PARAM_RULES: Dict[str, Tuple] = {
    # embedding / head
    "embedding": ("model", None),
    "lm_head": (None, "model"),
    # attention
    "wq": (None, "model"),
    "wk": (None, "model"),
    "wv": (None, "model"),
    "wo": ("model", None),
    # mlp
    "w_in": (None, "model"),
    "w_out": ("model", None),
    # moe experts (expert parallel)
    "w_up": ("model", None, None),
    "w_down": ("model", None, None),
    "router": (None, None),
    # mla
    "w_dq": (None, "model"),
    "w_uq": (None, "model"),
    "w_dkv": (None, None),
    "w_kr": (None, None),
    "w_uk": ("model", None, None),
    "w_uv": ("model", None, None),
    "w_o": ("model", None),
    # rglru
    "w_x": (None, "model"),
    "w_gate": (None, "model"),
    "w_a": (None, "model"),
    "w_i": (None, "model"),
    # ssm: w_in/w_out rules above; everything else replicated
    "mtp_proj": (None, "model"),
}


def _path_names(path) -> list:
    return [str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))) for p in path]


def _divisible(dim_size: int, axis_size: int) -> bool:
    return dim_size % axis_size == 0


def param_pspecs(cfg, params_tree, mesh, *, fsdp: bool = False):
    """PartitionSpec pytree matching ``params_tree`` (arrays or specs)."""
    model_n = mesh.shape["model"]
    data_n = mesh.shape.get("data", 1)

    # Attention projections are head-sharded; when the head count doesn't
    # divide the model axis, a raw column shard would cut across heads and
    # GSPMD de-shards the *batch* to compensate (hillclimb B, iteration 2:
    # 126 GiB/dev batch-replicated logits on internvl2's 14 heads @ 16-way).
    # Replicating the (small) attention weights keeps activations DP-clean.
    heads_ok = cfg.n_heads % model_n == 0
    kv_ok = cfg.n_kv_heads % model_n == 0

    def spec_for(path, leaf):
        names = _path_names(path)
        name = names[-1]
        shape = leaf.shape
        base = _PARAM_RULES.get(name)
        if name in ("wq", "wo") and not heads_ok:
            base = None
        if name in ("wk", "wv") and not (heads_ok and kv_ok):
            base = None
        if base is None or len(shape) < len(base):
            spec = [None] * len(shape)
        else:
            pad = len(shape) - len(base)
            spec = [None] * pad + list(base)
            # drop model sharding when the dim doesn't divide (GSPMD would
            # pad, but clean division keeps the roofline numbers honest)
            for i, ax in enumerate(spec):
                if ax == "model" and not _divisible(shape[i], model_n):
                    spec[i] = None
        if fsdp and len(shape) >= 2 and name not in ("embedding", "lm_head"):
            # NOTE (perf hillclimb C, iteration 2): the embedding/lm_head
            # tables are excluded — FSDP'ing their d_model dim makes the
            # embedding-gather output *feature*-sharded over `data`, which
            # silently batch-replicates every downstream activation
            # (measured: 128 GiB/dev f32 attention logits on deepseek).
            for i, ax in enumerate(spec):
                if ax is None and _divisible(shape[i], data_n) and shape[i] >= data_n * 8:
                    spec[i] = "data"
                    break
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, params_tree)


def batch_pspecs(cfg, batch_tree, mesh):
    """Batch dims over pod x data; everything else replicated."""
    dp = ("pod", "data") if "pod" in mesh.axis_names else "data"

    def spec_for(path, leaf):
        spec = [None] * len(leaf.shape)
        if len(spec) >= 1 and leaf.shape[0] % _dp_size(mesh) == 0:
            spec[0] = dp
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, batch_tree)


def _dp_size(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


def cache_pspecs(cfg, cache_tree, mesh, *, seq_shard: bool = False):
    """Decode-cache sharding.

    Default: batch over pod x data, kv-heads/channel dims over model.
    ``seq_shard=True`` (long_500k, batch=1): sequence dim over data instead.
    """
    dp = ("pod", "data") if "pod" in mesh.axis_names else "data"
    model_n = mesh.shape["model"]
    data_n = mesh.shape["data"]
    dp_n = _dp_size(mesh)

    def spec_for(path, leaf):
        names = _path_names(path)
        name = names[-1]
        shape = leaf.shape
        # stacked layer caches have a leading L dim; unrolled do not.
        stacked = names[0] in ("layers", "moe_layers") and cfg.scan_layers and \
            cfg.family in ("dense", "vlm", "moe", "ssm")
        off = 1 if stacked else 0
        spec = [None] * len(shape)
        bdim = off  # batch dim position
        if not seq_shard and shape[bdim] % dp_n == 0:
            spec[bdim] = dp
        if name in ("k", "v"):
            # (..., B, W, Kv, hd): prefer kv-heads over model; if the arch
            # has fewer kv heads than model shards, shard head_dim instead
            # (Megatron-style — the attention contraction all-reduces).
            wdim, kvdim, hdim = off + 1, off + 2, off + 3
            if seq_shard and shape[wdim] % data_n == 0:
                spec[wdim] = "data"
            if shape[kvdim] % model_n == 0:
                spec[kvdim] = "model"
            elif shape[hdim] % model_n == 0:
                spec[hdim] = "model"
        elif name in ("ckv", "kr"):
            wdim = off + 1
            if seq_shard and shape[wdim] % data_n == 0:
                spec[wdim] = "data"
        elif name == "state" and len(shape) - off == 4:
            # ssm state (..., B, H, P, N): heads over model
            if shape[off + 1] % model_n == 0:
                spec[off + 1] = "model"
        elif name == "state" and len(shape) - off == 2:
            # rglru state (..., B, dr): channels over model
            if shape[off + 1] % model_n == 0:
                spec[off + 1] = "model"
        elif name == "conv":
            # (..., B, W-1, C): channels over model
            if shape[-1] % model_n == 0:
                spec[-1] = "model"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, cache_tree)


def pspec_entries(pspec) -> Optional[Tuple]:
    """One ``MeshSpec`` var-spec from a ``PartitionSpec``.

    ``None`` means fully replicated; otherwise a per-dim tuple of axis
    name / tuple-of-names / ``None`` entries — the serializable spelling
    ``repro.core.meshspec.MeshSpec`` carries into the plan cache key.
    """
    entries = tuple(
        None if e is None else (e if isinstance(e, str) else tuple(e))
        for e in tuple(pspec)
    )
    return entries if any(e is not None for e in entries) else None


def mesh_spec_entries(pspec_tree) -> Tuple:
    """Flat per-leaf ``MeshSpec.in_specs`` rows from a PartitionSpec pytree
    (tree-flatten order, matching the compile pipeline's flat invars)."""
    leaves = jax.tree_util.tree_leaves(
        pspec_tree, is_leaf=lambda x: isinstance(x, P)
    )
    return tuple(pspec_entries(s) for s in leaves)


def to_shardings(mesh, pspec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def opt_state_pspecs(cfg, opt_state, mesh, *, fsdp: bool = False):
    """AdamW state: step replicated; moments mirror the param specs."""
    from ..optim import AdamWState

    mu = param_pspecs(cfg, opt_state.mu, mesh, fsdp=fsdp)
    nu = param_pspecs(cfg, opt_state.nu, mesh, fsdp=fsdp)
    return AdamWState(step=P(), mu=mu, nu=nu)
