"""Model assembly: init / forward / prefill / decode for every assigned arch.

All models are pure functions over parameter pytrees.  Homogeneous layer
stacks run under ``lax.scan`` (stacked params) so jaxprs stay compact and
AutoChunk is applied to the *block* function; heterogeneous stacks
(recurrentgemma's 1:2 pattern, deepseek's dense prefix) are unrolled.

Decode uses a ring-buffer KV cache of width W:  slot ``pos % W`` holds the
token at position ``p_i = pos - ((pos - i) mod W)``.  With W = max_len this
degenerates to the usual full cache; with W = sliding_window it is the
O(window) cache that makes ``long_500k`` feasible for dense archs.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig
from . import layers as L
from . import mla as MLA
from . import moe as MOE
from . import rglru as RG
from . import ssm as SSM


# ===========================================================================
# Parameter construction
# ===========================================================================

def _attn_block_params(cfg, key):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": L.norm_params(cfg, k1, cfg.d_model),
        "ln2": L.norm_params(cfg, k2, cfg.d_model),
        "attn": MLA.mla_params(cfg, k3) if cfg.mla else L.attn_params(cfg, k3),
    }
    return p, k4


def dense_block_params(cfg, key, d_ff=None):
    p, k = _attn_block_params(cfg, key)
    p["mlp"] = L.mlp_params(cfg, k, f=d_ff or cfg.d_ff)
    return p


def moe_block_params(cfg, key):
    p, k = _attn_block_params(cfg, key)
    p["moe"] = MOE.moe_params(cfg, k)
    return p


def ssm_block_params(cfg, key):
    k1, k2 = jax.random.split(key)
    return {"ln1": L.norm_params(cfg, k1, cfg.d_model), "ssm": SSM.ssm_params(cfg, k2)}


def rg_block_params(cfg, key):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": L.norm_params(cfg, k1, cfg.d_model),
        "ln2": L.norm_params(cfg, k2, cfg.d_model),
        "rec": RG.rglru_params(cfg, k3),
        "mlp": L.mlp_params(cfg, jax.random.fold_in(k3, 7)),
    }


def _layers(cfg, block_fn, keys):
    """Per-layer params: one stacked tree (built by ``vmap``, so a layer
    stack is one traced layer and one buffer per leaf) under
    ``scan_layers``, else a list of trees."""
    if cfg.scan_layers:
        return jax.vmap(block_fn)(keys)
    return [block_fn(k) for k in keys]


@partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    """Materialize parameters on the default device.

    Jitted, so a full-width model is built in place: the device holds one
    copy of the weights at peak, never per-layer copies plus their stack.
    """
    ks = jax.random.split(key, cfg.n_layers + 4)
    p: Dict[str, Any] = {"embed": L.embed_params(cfg, ks[0])}
    p["final_norm"] = L.norm_params(cfg, ks[1], cfg.d_model)
    layer_keys = ks[2: 2 + cfg.n_layers]

    fam = cfg.family
    if fam in ("dense", "vlm", "encoder", "audio"):
        p["blocks"] = _layers(
            cfg, lambda k: dense_block_params(cfg, k), layer_keys
        )
    elif fam == "moe":
        nd = cfg.first_k_dense
        p["dense_blocks"] = [
            dense_block_params(cfg, layer_keys[i], d_ff=cfg.d_ff)
            for i in range(nd)
        ]
        p["blocks"] = _layers(
            cfg, lambda k: moe_block_params(cfg, k), layer_keys[nd:]
        )
    elif fam == "ssm":
        p["blocks"] = _layers(
            cfg, lambda k: ssm_block_params(cfg, k), layer_keys
        )
    elif fam == "hybrid":
        p["blocks"] = [
            dense_block_params(cfg, layer_keys[i])
            if cfg.is_attention_layer(i)
            else rg_block_params(cfg, layer_keys[i])
            for i in range(cfg.n_layers)
        ]
    else:
        raise ValueError(fam)

    if cfg.mtp:
        p["mtp_proj"] = (
            jax.random.normal(ks[-2], (2 * cfg.d_model, cfg.d_model))
            / math.sqrt(2 * cfg.d_model)
        ).astype(cfg.jdtype)
        p["mtp_block"] = dense_block_params(cfg, ks[-1], d_ff=cfg.d_ff)
        p["mtp_norm"] = L.norm_params(cfg, ks[-1], cfg.d_model)
    return p


def param_specs(cfg: ModelConfig):
    """ShapeDtypeStruct pytree of the full parameterization (no allocation)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def param_count(cfg: ModelConfig) -> int:
    specs = param_specs(cfg)
    return sum(math.prod(x.shape) for x in jax.tree.leaves(specs))


def active_param_count(cfg: ModelConfig) -> int:
    """Active params per token (MoE: shared + top-k routed only)."""
    if not cfg.n_experts:
        return param_count(cfg)
    total = param_count(cfg)
    n_moe_layers = cfg.n_layers - cfg.first_k_dense
    per_expert = 3 * cfg.d_model * cfg.moe_d_ff  # w_up(2f) + w_down(f)
    inactive = n_moe_layers * per_expert * (
        cfg.n_experts_padded - cfg.experts_per_token
    )
    return total - inactive


# ===========================================================================
# Block applications (full-sequence)
# ===========================================================================

def attn_apply_full(cfg, p, x, positions=None, *, window, causal):
    if positions is None:
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    h = L.apply_norm(cfg, x, p["ln1"])
    if cfg.mla:
        o, _ = MLA.mla_attention_prefill(cfg, p["attn"], h, positions, window=window)
    else:
        q, k, v = L.attn_project_qkv(cfg, p["attn"], h, positions)
        o = L.gqa_attention(
            q, k, v, q_pos=positions, kv_pos=positions, causal=causal, window=window
        )
        o = o.reshape(x.shape[0], x.shape[1], -1) @ p["attn"]["wo"]
    return x + o


def dense_block_full(cfg, p, x, positions=None, *, window=None, causal=None):
    causal = cfg.causal if causal is None else causal
    x = attn_apply_full(cfg, p, x, positions, window=window, causal=causal)
    h = L.apply_norm(cfg, x, p["ln2"])
    return x + L.mlp(cfg, p["mlp"], h)


def moe_block_full(cfg, p, x, positions=None, *, window=None):
    x = attn_apply_full(cfg, p, x, positions, window=window, causal=True)
    h = L.apply_norm(cfg, x, p["ln2"])
    ff, aux = MOE.moe_ffn(cfg, p["moe"], h)
    return x + ff, aux


def ssm_block_full(cfg, p, x):
    h = L.apply_norm(cfg, x, p["ln1"])
    y, _ = SSM.ssm_block(cfg, p["ssm"], h)
    return x + y


def rg_block_full(cfg, p, x):
    h = L.apply_norm(cfg, x, p["ln1"])
    y, _ = RG.recurrent_block(cfg, p["rec"], h)
    x = x + y
    h = L.apply_norm(cfg, x, p["ln2"])
    return x + L.mlp(cfg, p["mlp"], h)


# ===========================================================================
# Embedding of model inputs (tokens / audio frames / vision patches)
# ===========================================================================

def embed_inputs(cfg, params, batch: Dict[str, Any]):
    """Returns (h (B,S,d), positions (S,))."""
    if cfg.family == "audio":
        h = batch["frames"].astype(cfg.jdtype)  # stub frontend embeddings
    elif cfg.family == "vlm":
        text = L.embed(cfg, params["embed"], batch["tokens"])
        patches = batch["patches"].astype(cfg.jdtype)  # stub ViT embeddings
        h = jnp.concatenate([patches, text], axis=1)
    else:
        h = L.embed(cfg, params["embed"], batch["tokens"])
    positions = jnp.arange(h.shape[1], dtype=jnp.int32)
    return h, positions


# ===========================================================================
# Full-sequence forward (training / prefill-logits / encoder)
# ===========================================================================

# Optional activation-sharding hook (set by the launcher under a mesh):
# GSPMD's propagation sometimes re-shards the residual stream away from
# data parallelism (measured: batch-replicated 126 GiB/dev f32 attention
# logits on internvl2 train).  Pinning (B, S, d) activations at block
# boundaries — the MaxText pattern — keeps propagation honest.
_ACT_CONSTRAINT = None


def set_activation_constraint(fn):
    """fn(x) -> x with a sharding constraint applied (or None to clear)."""
    global _ACT_CONSTRAINT
    _ACT_CONSTRAINT = fn


def _constrain(x):
    if _ACT_CONSTRAINT is not None and getattr(x, "ndim", 0) == 3:
        return _ACT_CONSTRAINT(x)
    return x


# AutoChunk is a first-class config feature: when cfg.autochunk_budget is
# set, block functions are compiled through the AutoChunk pipeline (keyed by
# arch/shape so the search runs once, not per layer / per trace).
_AC_CACHE: Dict[Any, Any] = {}


def _maybe_autochunk(cfg, tag: str, fn, args):
    if not cfg.autochunk_budget:
        return fn
    from ..core import ChunkConfig, autochunk

    # one ChunkedFunction per (config, budget, block): it compiles lazily per
    # input shape and replays one searched plan across every sequence length
    # in the same bucket, so a length sweep pays a single search.  The full
    # (frozen, hashable) cfg is part of the key because ``fn`` closes over
    # it — two reduced variants sharing a name must not share closures.
    key = (cfg.name, cfg.autochunk_budget, tag, cfg)
    if key not in _AC_CACHE:
        chunk_cfg = ChunkConfig.from_scalar(
            cfg.autochunk_budget,
            weight_argnums=(0,),
            # dim 0 of every activation is the data-parallel batch axis;
            # chunking it would fight the mesh sharding (see core/search.py)
            dim_blocklist=(0,),
        )
        _AC_CACHE[key] = autochunk(fn, chunk_cfg)
    return _AC_CACHE[key]


def autochunk_results(cfg: ModelConfig) -> list:
    """Compile reports (``AutoChunkResult``) of the AutoChunk'd block
    functions ``forward`` built for ``cfg`` so far, most recent per block."""
    return [
        cf.autochunk_result for key, cf in _AC_CACHE.items()
        if key[3] == cfg and cf.autochunk_result is not None
    ]


def forward(cfg: ModelConfig, params, batch, *, window=None, remat: bool = False):
    """Full-sequence forward.  Returns (logits, aux_loss_scalar)."""
    h, aux = forward_hidden(cfg, params, batch, window=window, remat=remat)
    return L.unembed(cfg, params["embed"], h), aux


def next_token_logits(cfg: ModelConfig, params, batch, *, window=None):
    """Prefill semantics: logits (B, V) of the last position only.

    Only that position is unembedded, so no (B, S, V) logits array exists —
    at a 256k vocabulary it would outweigh the attention activations.
    """
    h, _ = forward_hidden(cfg, params, batch, window=window)
    return L.unembed(cfg, params["embed"], h[:, -1])


def forward_hidden(cfg: ModelConfig, params, batch, *, window=None,
                   remat: bool = False):
    """Full-sequence forward up to the final norm.  Returns (h, aux)."""
    h, positions = embed_inputs(cfg, params, batch)
    aux = jnp.zeros((), jnp.float32)
    fam = cfg.family

    h = _constrain(h)

    def wrap(tag, fn, example):
        fn = _maybe_autochunk(cfg, tag, fn, example)
        if remat:
            fn = jax.checkpoint(fn)
        inner = fn
        def constrained(p, x, _inner=inner):
            out = _inner(p, x)
            if isinstance(out, tuple):
                return (_constrain(out[0]),) + out[1:]
            return _constrain(out)
        return constrained

    if fam in ("dense", "vlm", "encoder", "audio"):
        p0 = (
            jax.tree.map(lambda a: a[0], params["blocks"])
            if cfg.scan_layers
            else params["blocks"][0]
        )
        fn = wrap(
            f"dense{window}",
            lambda p, x: dense_block_full(cfg, p, x, window=window, causal=cfg.causal),
            (p0, h),
        )
        step = lambda x, p: (fn(p, x), None)
        if cfg.scan_layers:
            h, _ = lax.scan(step, h, params["blocks"])
        else:
            for p in params["blocks"]:
                h, _ = step(h, p)

    elif fam == "moe":
        if params["dense_blocks"]:
            dfn = wrap(
                f"densepre{window}",
                lambda p, x: dense_block_full(cfg, p, x, window=window),
                (params["dense_blocks"][0], h),
            )
            for p in params["dense_blocks"]:
                h = dfn(p, h)
        p0 = (
            jax.tree.map(lambda a: a[0], params["blocks"])
            if cfg.scan_layers
            else params["blocks"][0]
        )
        mfn = wrap(
            f"moe{window}",
            lambda p, x: moe_block_full(cfg, p, x, window=window),
            (p0, h),
        )

        def moe_step(carry, p):
            x, a = carry
            x, aux_i = mfn(p, x)
            return (x, a + aux_i), None

        if cfg.scan_layers:
            (h, aux), _ = lax.scan(moe_step, (h, aux), params["blocks"])
        else:
            for p in params["blocks"]:
                (h, aux), _ = moe_step((h, aux), p)

    elif fam == "ssm":
        p0 = (
            jax.tree.map(lambda a: a[0], params["blocks"])
            if cfg.scan_layers
            else params["blocks"][0]
        )
        fn = wrap("ssm", lambda p, x: ssm_block_full(cfg, p, x), (p0, h))
        step = lambda x, p: (fn(p, x), None)
        if cfg.scan_layers:
            h, _ = lax.scan(step, h, params["blocks"])
        else:
            for p in params["blocks"]:
                h, _ = step(h, p)

    elif fam == "hybrid":
        attn_idx = [i for i in range(cfg.n_layers) if cfg.is_attention_layer(i)]
        rg_idx = [i for i in range(cfg.n_layers) if not cfg.is_attention_layer(i)]
        afn = wrap(
            "hyb_attn",
            lambda p, x: dense_block_full(cfg, p, x, window=cfg.local_window),
            (params["blocks"][attn_idx[0]], h),
        ) if attn_idx else None
        rfn = wrap(
            "hyb_rg", lambda p, x: rg_block_full(cfg, p, x),
            (params["blocks"][rg_idx[0]], h),
        ) if rg_idx else None
        for i, p in enumerate(params["blocks"]):
            h = afn(p, h) if cfg.is_attention_layer(i) else rfn(p, h)
    else:
        raise ValueError(fam)

    return L.apply_norm(cfg, h, params["final_norm"]), aux


def mtp_logits(cfg, params, batch, h_final):
    """DeepSeek-V3 MTP head: predict token t+2 from (h_t, emb_{t+1})."""
    tokens = batch["tokens"]
    emb_next = L.embed(cfg, params["embed"], tokens[:, 1:])
    h_in = jnp.concatenate(
        [L.apply_norm(cfg, h_final[:, :-1], params["mtp_norm"]), emb_next], axis=-1
    ) @ params["mtp_proj"]
    positions = jnp.arange(h_in.shape[1], dtype=jnp.int32)
    h = dense_block_full(cfg, params["mtp_block"], h_in, positions)
    return L.unembed(cfg, params["embed"], h)


# ===========================================================================
# KV / state caches
# ===========================================================================

def cache_width(cfg: ModelConfig, max_len: int) -> int:
    if cfg.sliding_window is not None and max_len > cfg.sliding_window:
        return cfg.sliding_window
    return max_len


def layer_cache_spec(cfg, kind: str, batch: int, width: int):
    dt = cfg.jdtype
    if kind == "attn":
        if cfg.mla:
            return {
                "ckv": jax.ShapeDtypeStruct((batch, width, cfg.kv_lora_rank), dt),
                "kr": jax.ShapeDtypeStruct((batch, width, cfg.qk_rope_dim), dt),
            }
        return {
            "k": jax.ShapeDtypeStruct((batch, width, cfg.n_kv_heads, cfg.hd), dt),
            "v": jax.ShapeDtypeStruct((batch, width, cfg.n_kv_heads, cfg.hd), dt),
        }
    if kind == "local_attn":
        w = min(width, cfg.local_window)
        return {
            "k": jax.ShapeDtypeStruct((batch, w, cfg.n_kv_heads, cfg.hd), dt),
            "v": jax.ShapeDtypeStruct((batch, w, cfg.n_kv_heads, cfg.hd), dt),
        }
    if kind == "ssm":
        st, cv = SSM.ssm_state_specs(cfg, batch)
        return {"state": st, "conv": cv}
    if kind == "rglru":
        st, cv = RG.rglru_state_specs(cfg, batch)
        return {"state": st, "conv": cv}
    raise ValueError(kind)


def layer_kinds(cfg: ModelConfig):
    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        return ["attn"] * cfg.n_layers
    if fam == "ssm":
        return ["ssm"] * cfg.n_layers
    if fam == "hybrid":
        return [
            "local_attn" if cfg.is_attention_layer(i) else "rglru"
            for i in range(cfg.n_layers)
        ]
    raise ValueError(f"{fam} has no decode cache")


def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    width = cache_width(cfg, max_len)
    kinds = layer_kinds(cfg)
    per_layer = [layer_cache_spec(cfg, k, batch, width) for k in kinds]
    if cfg.scan_layers and cfg.family in ("dense", "vlm", "ssm"):
        return {"layers": jax.tree.map(
            lambda *xs: jax.ShapeDtypeStruct((len(per_layer),) + xs[0].shape, xs[0].dtype),
            *per_layer,
        )}
    if cfg.scan_layers and cfg.family == "moe":
        dense, moe_layers = per_layer[: cfg.first_k_dense], per_layer[cfg.first_k_dense:]
        out = {"moe_layers": jax.tree.map(
            lambda *xs: jax.ShapeDtypeStruct((len(moe_layers),) + xs[0].shape, xs[0].dtype),
            *moe_layers,
        )}
        if dense:
            out["dense_layers"] = dense
        return out
    return {"layers": per_layer}


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    specs = cache_specs(cfg, batch, max_len)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)


# ===========================================================================
# Decode step (serving): one token against the ring cache
# ===========================================================================

def _ring_kv_positions(pos, width):
    i = jnp.arange(width, dtype=jnp.int32)
    return pos - jnp.mod(pos - i, width)


def attn_block_decode(cfg, p, x, cache, pos, *, window=None, local=False):
    """x: (B,1,d).  Returns (y, new_cache)."""
    B = x.shape[0]
    h = L.apply_norm(cfg, x, p["ln1"])
    if cfg.mla:
        width = cache["ckv"].shape[1]
        slot = jnp.mod(pos, width)
        kv_pos = _ring_kv_positions(pos, width)
        # compute this token's latent and insert BEFORE attending
        new_ckv, new_kr = MLA.mla_latent(
            cfg, p["attn"], h, jnp.full((B, 1), pos, jnp.int32)
        )
        ckv = lax.dynamic_update_slice(cache["ckv"], new_ckv, (0, slot, 0))
        kr = lax.dynamic_update_slice(cache["kr"], new_kr, (0, slot, 0))
        valid = kv_pos >= 0
        o, _ = MLA.mla_attention_decode(cfg, p["attn"], h, ckv, kr, pos, valid)
        x = x + o
        new_cache = {"ckv": ckv, "kr": kr}
    else:
        width = cache["k"].shape[1]
        slot = jnp.mod(pos, width)
        kv_pos = _ring_kv_positions(pos, width)
        q, k, v = L.attn_project_qkv(
            cfg, p["attn"], h, jnp.full((1,), pos, jnp.int32)
        )
        ck = lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        cv = lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
        valid = kv_pos >= 0
        win = cfg.local_window if local else window
        o = L.gqa_attention(
            q, ck, cv,
            q_pos=jnp.full((1,), pos, jnp.int32), kv_pos=kv_pos,
            causal=True, window=win, kv_valid=valid,
        )
        o = o.reshape(B, 1, -1) @ p["attn"]["wo"]
        x = x + o
        new_cache = {"k": ck, "v": cv}
    return x, new_cache


def dense_block_decode(cfg, p, x, cache, pos, *, window=None, local=False):
    x, new_cache = attn_block_decode(cfg, p, x, cache, pos, window=window, local=local)
    h = L.apply_norm(cfg, x, p["ln2"])
    return x + L.mlp(cfg, p["mlp"], h), new_cache


def moe_block_decode(cfg, p, x, cache, pos, *, window=None):
    x, new_cache = attn_block_decode(cfg, p, x, cache, pos, window=window)
    h = L.apply_norm(cfg, x, p["ln2"])
    ff, _ = MOE.moe_ffn(cfg, p["moe"], h)
    return x + ff, new_cache


def ssm_block_decode(cfg, p, x, cache):
    h = L.apply_norm(cfg, x, p["ln1"])
    y, (st, cv) = SSM.ssm_block(
        cfg, p["ssm"], h, state=cache["state"], conv_state=cache["conv"], decode=True
    )
    return x + y, {"state": st, "conv": cv}


def rg_block_decode(cfg, p, x, cache):
    h = L.apply_norm(cfg, x, p["ln1"])
    y, (st, cv) = RG.recurrent_block(
        cfg, p["rec"], h, state=cache["state"], conv_state=cache["conv"], decode=True
    )
    x = x + y
    h = L.apply_norm(cfg, x, p["ln2"])
    return x + L.mlp(cfg, p["mlp"], h), cache | {"state": st, "conv": cv}


def decode_step(cfg: ModelConfig, params, cache, tokens, pos, *, window=None):
    """One serving step: tokens (B,1) int32, pos scalar int32.

    Returns (logits (B,1,V), new_cache)."""
    if window is None:
        window = cfg.sliding_window
    h = L.embed(cfg, params["embed"], tokens)
    fam = cfg.family

    if fam in ("dense", "vlm"):
        if cfg.scan_layers:
            def body(x, inp):
                p, c = inp
                x, nc = dense_block_decode(cfg, p, x, c, pos, window=window)
                return x, nc
            h, new_layers = lax.scan(body, h, (params["blocks"], cache["layers"]))
            new_cache = {"layers": new_layers}
        else:
            new_list = []
            for p, c in zip(params["blocks"], cache["layers"]):
                h, nc = dense_block_decode(cfg, p, h, c, pos, window=window)
                new_list.append(nc)
            new_cache = {"layers": new_list}

    elif fam == "moe":
        new_dense = []
        for p, c in zip(params["dense_blocks"], cache.get("dense_layers", [])):
            h, nc = dense_block_decode(cfg, p, h, c, pos, window=window)
            new_dense.append(nc)

        def body(x, inp):
            p, c = inp
            x, nc = moe_block_decode(cfg, p, x, c, pos, window=window)
            return x, nc

        if cfg.scan_layers:
            h, new_moe = lax.scan(body, h, (params["blocks"], cache["moe_layers"]))
            new_cache = {"moe_layers": new_moe}
        else:
            new_moe = []
            for p, c in zip(params["blocks"], cache["moe_layers"]):
                h, nc = body(h, (p, c))
                new_moe.append(nc)
            new_cache = {"moe_layers": new_moe}
        if new_dense:
            new_cache["dense_layers"] = new_dense

    elif fam == "ssm":
        def body(x, inp):
            p, c = inp
            x, nc = ssm_block_decode(cfg, p, x, c)
            return x, nc
        if cfg.scan_layers:
            h, new_layers = lax.scan(body, h, (params["blocks"], cache["layers"]))
            new_cache = {"layers": new_layers}
        else:
            new_list = []
            for p, c in zip(params["blocks"], cache["layers"]):
                h, nc = body(h, (p, c))
                new_list.append(nc)
            new_cache = {"layers": new_list}

    elif fam == "hybrid":
        new_list = []
        for i, (p, c) in enumerate(zip(params["blocks"], cache["layers"])):
            if cfg.is_attention_layer(i):
                h, nc = dense_block_decode(cfg, p, h, c, pos, local=True)
            else:
                h, nc = rg_block_decode(cfg, p, h, c)
            new_list.append(nc)
        new_cache = {"layers": new_list}
    else:
        raise ValueError(f"decode unsupported for family {fam}")

    h = L.apply_norm(cfg, h, params["final_norm"])
    logits = L.unembed(cfg, params["embed"], h)
    return logits, new_cache


# ===========================================================================
# Prefill: full-sequence forward that also fills the decode cache
# ===========================================================================

def prefill(cfg: ModelConfig, params, batch, max_len: int, *, window=None):
    """Run the full prompt, return (logits, cache filled up to S)."""
    if window is None:
        window = cfg.sliding_window
    B = jax.tree.leaves(batch)[0].shape[0]
    S = (batch["tokens"].shape[1] if "tokens" in batch else batch["frames"].shape[1])
    cache = init_cache(cfg, B, max_len)
    logits, _ = forward(cfg, params, batch, window=window)
    # Fill attention caches by recomputing k/v per layer (cheap projections).
    h, positions = embed_inputs(cfg, params, batch)
    width = cache_width(cfg, max_len)
    fam = cfg.family

    def fill_kv(p, h_in):
        hn = L.apply_norm(cfg, h_in, p["ln1"])
        if cfg.mla:
            ckv, kr = MLA.mla_latent(cfg, p["attn"], hn, positions)
            return {"ckv": ckv, "kr": kr}
        _, k, v = L.attn_project_qkv(cfg, p["attn"], hn, positions)
        return {"k": k, "v": v}

    # For correctness-tested serving we re-run the stack block by block,
    # capturing caches (hybrid/ssm states included).
    if fam in ("dense", "vlm", "moe"):
        blocks = params["blocks"]
        caches = []
        hs = h
        dense_caches = []
        if fam == "moe":
            for p in params["dense_blocks"]:
                c = fill_kv(p, hs)
                hs = dense_block_full(cfg, p, hs, positions, window=window)
                dense_caches.append(_pad_kv(c, width, S))
            if cfg.scan_layers:
                def body(x, p):
                    c = fill_kv(p, x)
                    x2, _ = moe_block_full(cfg, p, x, positions, window=window)
                    return x2, _pad_kv(c, width, S)
                hs, moe_caches = lax.scan(body, hs, blocks)
                cache = {"moe_layers": moe_caches}
                if dense_caches:
                    cache["dense_layers"] = dense_caches
            else:
                raise NotImplementedError
        else:
            if cfg.scan_layers:
                def body(x, p):
                    c = fill_kv(p, x)
                    x2 = dense_block_full(cfg, p, x, positions, window=window,
                                          causal=cfg.causal)
                    return x2, _pad_kv(c, width, S)
                hs, layer_caches = lax.scan(body, h, blocks)
                cache = {"layers": layer_caches}
            else:
                caches = []
                for p in blocks:
                    c = fill_kv(p, hs)
                    hs = dense_block_full(cfg, p, hs, positions, window=window)
                    caches.append(_pad_kv(c, width, S))
                cache = {"layers": caches}
    elif fam == "ssm":
        def body(x, p):
            hn = L.apply_norm(cfg, x, p["ln1"])
            y, (st, cv) = SSM.ssm_block(cfg, p["ssm"], hn)
            return x + y, {"state": st, "conv": cv}
        hs, layer_caches = lax.scan(body, h, params["blocks"])
        cache = {"layers": layer_caches}
    elif fam == "hybrid":
        caches = []
        hs = h
        for i, p in enumerate(params["blocks"]):
            if cfg.is_attention_layer(i):
                c = fill_kv(p, hs)
                w = min(width, cfg.local_window)
                caches.append(_pad_kv(c, w, S))
                hs = dense_block_full(cfg, p, hs, positions, window=cfg.local_window)
            else:
                hn = L.apply_norm(cfg, hs, p["ln1"])
                y, (st, cv) = RG.recurrent_block(cfg, p["rec"], hn)
                x2 = hs + y
                hn2 = L.apply_norm(cfg, x2, p["ln2"])
                hs = x2 + L.mlp(cfg, p["mlp"], hn2)
                caches.append({"state": st, "conv": cv})
        cache = {"layers": caches}
    else:
        raise ValueError(fam)
    return logits, cache


def _pad_kv(c, width: int, S: int):
    """Place the last min(S,width) positions into the ring layout."""
    def fix(x):
        if x.ndim < 2 or x.shape[1] == width:
            return x
        if x.shape[1] > width:  # keep the window tail, ring-aligned
            tail = x[:, -width:]
            # position of tail[j] is S - width + j; its slot is pos % width
            shift = (S - width) % width
            return jnp.roll(tail, shift, axis=1)
        pad = [(0, 0)] * x.ndim
        pad[1] = (0, width - x.shape[1])
        return jnp.pad(x, pad)
    return jax.tree.map(fix, c)
