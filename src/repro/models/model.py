"""The language model: embedding -> pattern-group scan -> head.

Three entry points (DESIGN.md §7):
  * ``forward_train``  — full-sequence forward returning the streamed
    (chunked-over-sequence) cross-entropy loss; logits [B,S,V] are never
    materialized (the paper's streaming idea applied to the loss).
  * ``prefill``        — full-sequence forward returning last-position logits
    and the decode caches (KV / SSM state / RWKV state).
  * ``decode_step``    — one token against the caches.

Layers are applied as a ``lax.scan`` over *pattern groups* (stacked params
from ``params.py``), keeping the HLO small and compile times manageable at
54 layers; remainder layers run unrolled.  Zamba2's shared attention block is
closed over by the scan body (single parameter copy, per-application caches).
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
from .params import padded_vocab

Tree = Any
Plan = Any          # core.stream_plan.StreamPlan (imported lazily)
LPlan = Any         # core.stream_plan.LayerPlan


def resolve_plan(cfg: ModelConfig, tokens: int, *,
                 kv_len: Optional[int] = None,
                 plan: Optional[Plan] = None,
                 mesh=None) -> Optional[Plan]:
    """The StreamPlan driving fused-kernel dispatch, or None for eager.

    An explicit ``plan`` wins; otherwise ``cfg.use_fused_kernels`` triggers
    the (cached) compiler pipeline in ``core.stream_plan``.  Resolution
    happens at trace time — the plan is static under jit.  ``mesh``
    defaults to the active ``distributed.context`` mesh, so entry points
    traced under ``use_mesh(...)`` get mesh-aware plans (per-stage
    sharding decisions the fused wrappers turn into ``shard_map``)
    without any caller churn.
    """
    if plan is not None:
        return plan
    if not cfg.use_fused_kernels:
        return None
    if mesh is None:
        from ..distributed.context import current_mesh
        mesh = current_mesh()
    from ..core.stream_plan import plan_for
    return plan_for(cfg, tokens, kv_len, mesh)


def _lplan(plan: Optional[Plan], kind: str) -> Optional[LPlan]:
    return plan.layer(kind) if plan is not None else None


def _cache_kv_len(cfg: ModelConfig, cache: Tree,
                  page_table: Optional[jax.Array] = None) -> Optional[int]:
    """Max KV length held by a decode cache (None for pure SSM caches).

    Stacked K leaves are [G, B, S, Hkv, hd] ("bshd") or [G, B, Hkv, S, hd]
    ("bhsd"); paged K leaves are pools [G, P, Hkv, page_size, hd] and the
    extent is the page table's ``max_pages * page_size``.  Used so the
    decode plan's DSE models attention over the real cache extent rather
    than the (tiny) per-step token count.
    """
    from .params import cache_leaf_kind, cache_leaf_name, kv_seq_axis
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if cache_leaf_kind(cache_leaf_name(path)) == "kv":
            if page_table is not None:
                return int(page_table.shape[1]) * int(leaf.shape[3])
            return int(leaf.shape[kv_seq_axis(cfg.kv_cache_layout)])
    return None


def _c(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Cast to compute dtype (bf16); norms re-promote internally."""
    return x.astype(jnp.bfloat16) if cfg.dtype == "bfloat16" else x


def _cast_tree(cfg: ModelConfig, t: Tree) -> Tree:
    return jax.tree.map(lambda a: _c(cfg, a) if a.dtype == jnp.float32 else a,
                        t)


def _chunk_of(n: int, want: int) -> int:
    c = min(want, n)
    while n % c != 0:
        c = math.gcd(n, c)
    return max(1, c)


# --------------------------------------------------------------------- #
# Block application (full-sequence mode)
# --------------------------------------------------------------------- #

def _qk_normed(cfg: ModelConfig, p: Tree, q: jax.Array,
               k: jax.Array) -> Tuple[jax.Array, jax.Array]:
    if not cfg.qk_norm:
        return q, k
    return (L.rms_norm(q, p["q_norm"]), L.rms_norm(k, p["k_norm"]))


def _project_qkv(cfg: ModelConfig, p: Tree, x: jax.Array, ln_p: Tree,
                 lplan: Optional[LPlan],
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """ln + Q/K/V projections, eager or plan-fused.

    With ``rmsnorm_matmul`` the norm is folded into each projection (norm
    stats recomputed per kernel — VPU work traded for the HBM round-trip of
    the normalized stream); with ``block_matmul`` the norm stays eager and
    the projections run through the tiled Pallas matmul.
    """
    choice = lplan.qkv if lplan is not None else None
    if choice is not None and choice.fused:
        kw = choice.kw
        if choice.implementation == "rmsnorm_matmul":
            q = L.fused_norm_matmul(x, ln_p["scale"], p["wq"], **kw)
            k = L.fused_norm_matmul(x, ln_p["scale"], p["wk"], **kw)
            v = L.fused_norm_matmul(x, ln_p["scale"], p["wv"], **kw)
        else:
            h = L.apply_norm(cfg.norm, x, ln_p)
            q = L.fused_matmul(h, p["wq"], **kw)
            k = L.fused_matmul(h, p["wk"], **kw)
            v = L.fused_matmul(h, p["wv"], **kw)
    else:
        h = L.apply_norm(cfg.norm, x, ln_p)
        q = h @ p["wq"]
        k = h @ p["wk"]
        v = h @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


def _attn_full(cfg: ModelConfig, p: Tree, x: jax.Array, ln_p: Tree,
               positions: jax.Array, *, window: int, collect: bool,
               lplan: Optional[LPlan] = None,
               ) -> Tuple[jax.Array, Optional[Tree]]:
    b, s, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    q, k, v = _project_qkv(cfg, p, x, ln_p, lplan)
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    q, k = _qk_normed(cfg, p, q, k)
    q = L.apply_positional(cfg.rope, q, positions, cfg.rope_theta)
    k = L.apply_positional(cfg.rope, k, positions, cfg.rope_theta)
    attn_c = lplan.attention if lplan is not None else None
    if attn_c is not None and attn_c.fused:
        o = L.fused_attention(q, k, v, causal=cfg.causal, window=window,
                              **attn_c.kw)
    elif window:
        o = L.local_attention(q, k, v, window=window,
                              remat_chunk=cfg.remat_attn_chunk)
    else:
        o = L.streaming_attention(q, k, v, causal=cfg.causal,
                                  remat_chunk=cfg.remat_attn_chunk)
    out = o.reshape(b, s, hq * hd) @ p["wo"]
    if collect:
        if cfg.kv_cache_layout == "bhsd":
            return out, {"k": k.transpose(0, 2, 1, 3),
                         "v": v.transpose(0, 2, 1, 3)}
        return out, {"k": k, "v": v}
    return out, None


def _ffn_apply(cfg: ModelConfig, p: Tree, x: jax.Array) -> jax.Array:
    if cfg.is_moe:
        return L.moe_ffn(x, p, activation=cfg.activation,
                         gated=cfg.gated_ffn, num_experts=cfg.num_experts,
                         top_k=cfg.top_k)
    return L.ffn(x, p, activation=cfg.activation, gated=cfg.gated_ffn)


def _ffn_block(cfg: ModelConfig, p: Tree, x: jax.Array, ln_p: Tree,
               lplan: Optional[LPlan]) -> jax.Array:
    """ln2 + FFN/MoE, eager or plan-fused.  ``fuse_norm`` in the choice
    folds the RMSNorm into the streamed FFN kernel itself."""
    choice = lplan.ffn if lplan is not None else None
    if choice is not None and choice.fused:
        kw = choice.kw
        if choice.implementation == "moe_experts":
            h2 = L.apply_norm(cfg.norm, x, ln_p)
            return L.fused_moe_ffn(h2, p, activation=cfg.activation,
                                   top_k=cfg.top_k, **kw)
        fuse_norm = bool(kw.pop("fuse_norm", 0))
        if fuse_norm:
            return L.fused_ffn(x, p, activation=cfg.activation,
                               gated=cfg.gated_ffn,
                               norm_scale=ln_p["scale"], **kw)
        h2 = L.apply_norm(cfg.norm, x, ln_p)
        return L.fused_ffn(h2, p, activation=cfg.activation,
                           gated=cfg.gated_ffn, **kw)
    h2 = L.apply_norm(cfg.norm, x, ln_p)
    return _ffn_apply(cfg, p, h2)


def _attn_block_full(cfg: ModelConfig, p: Tree, x: jax.Array,
                     positions: jax.Array, *, window: int = 0,
                     collect: bool = False,
                     lplan: Optional[LPlan] = None,
                     ) -> Tuple[jax.Array, Optional[Tree]]:
    attn_out, kv = _attn_full(cfg, p["attn"], x, p["ln1"], positions,
                              window=window, collect=collect, lplan=lplan)
    x = x + attn_out
    x = x + _ffn_block(cfg, p["mlp"], x, p["ln2"], lplan)
    return x, kv


def _mamba_block_full(cfg: ModelConfig, p: Tree, x: jax.Array, *,
                      collect: bool = False,
                      lplan: Optional[LPlan] = None,
                      ) -> Tuple[jax.Array, Optional[Tree]]:
    b, s, d = x.shape
    m = p["mamba"]
    h = L.apply_norm(cfg.norm, x, p["ln"])
    xin = h @ m["wx"]                                      # [B,S,di]
    z = h @ m["wz"]
    bmat = h @ m["wb"]                                     # [B,S,N]
    cmat = h @ m["wc"]
    dt = jax.nn.softplus(h @ m["wdt"]
                         + m["dt_bias"].astype(h.dtype))   # [B,S,H]
    xconv, conv_tail = L.causal_conv1d(xin, m["conv_w"], m["conv_b"])
    hps = xconv.reshape(b, s, cfg.ssm_heads, cfg.ssm_head_dim)
    mixer = lplan.mixer if lplan is not None else None
    if mixer is not None and mixer.fused:
        chunk = _chunk_of(s, mixer.kw.get("chunk", 128))
        y, state = L.fused_mamba2_ssd(hps, dt, m["a_log"], bmat, cmat,
                                      m["d_skip"], chunk=chunk,
                                      shard=mixer.sharding)
    else:
        chunk = _chunk_of(s, 128)
        y, state = L.mamba2_ssd(hps, dt, m["a_log"], bmat, cmat,
                                m["d_skip"], chunk=chunk)
    y = y.reshape(b, s, cfg.d_inner) * jax.nn.silu(z)
    x = x + y @ m["wout"]
    aux = {"ssm": state.astype(jnp.float32),
           "conv": conv_tail} if collect else None
    return x, aux


def _rwkv_block_full(cfg: ModelConfig, p: Tree, x: jax.Array, *,
                     collect: bool = False,
                     lplan: Optional[LPlan] = None,
                     ) -> Tuple[jax.Array, Optional[Tree]]:
    b, s, d = x.shape
    h, n = cfg.rwkv_heads, cfg.rwkv_head_dim
    tm, cm = p["tm"], p["cm"]
    # Time mix.
    xa = L.apply_norm(cfg.norm, x, p["ln1"])
    xs = L.token_shift(xa)

    def mix(name):
        mu = tm[f"mix_{name}"].astype(xa.dtype)
        return xa * mu + xs * (1.0 - mu)

    r = (mix("r") @ tm["wr"]).reshape(b, s, h, n)
    k = (mix("k") @ tm["wk"]).reshape(b, s, h, n)
    v = (mix("v") @ tm["wv"]).reshape(b, s, h, n)
    g = jax.nn.silu(mix("g") @ tm["wg"])
    wdec = jnp.exp(-jnp.exp(
        (mix("w") @ tm["ww"]).astype(jnp.float32)
        + tm["w_bias"].reshape(1, 1, h * n))).reshape(b, s, h, n)
    mixer = lplan.mixer if lplan is not None else None
    if mixer is not None and mixer.fused:
        y, state = L.fused_wkv6(r, k, v, wdec, tm["u"],
                                chunk=_chunk_of(s, mixer.kw.get("chunk", 64)),
                                shard=mixer.sharding)
    elif cfg.rwkv_chunk > 0:
        y, state = L.wkv6_chunked(r, k, v, wdec, tm["u"],
                                  chunk=cfg.rwkv_chunk)
    else:
        y, state = L.wkv6(r, k, v, wdec, tm["u"])
    y = (y.reshape(b, s, d) * g) @ tm["wo"]
    x = x + y
    # Channel mix.
    xc = L.apply_norm(cfg.norm, x, p["ln2"])
    xcs = L.token_shift(xc)

    def cmix(name):
        mu = cm[f"mix_{name}"].astype(xc.dtype)
        return xc * mu + xcs * (1.0 - mu)

    kk = jnp.square(jax.nn.relu(cmix("k") @ cm["wk"]))
    rr = jax.nn.sigmoid(cmix("r") @ cm["wr"])
    x = x + rr * (kk @ cm["wv"])
    aux = None
    if collect:
        aux = {"wkv": state, "tm_shift": xa[:, -1], "cm_shift": xc[:, -1]}
    return x, aux


def _apply_block_full(cfg: ModelConfig, kind: str, p: Tree, shared: Tree,
                      x: jax.Array, positions: jax.Array,
                      collect: bool,
                      lplan: Optional[LPlan] = None) -> Tuple[jax.Array, Tree]:
    if kind == "rwkv":
        return _rwkv_block_full(cfg, p, x, collect=collect, lplan=lplan)
    if kind == "mamba":
        return _mamba_block_full(cfg, p, x, collect=collect, lplan=lplan)
    if kind == "mamba+shared_attn":
        x, aux = _mamba_block_full(cfg, p, x, collect=collect, lplan=lplan)
        x, kv = _attn_block_full(cfg, shared, x, positions, collect=collect,
                                 lplan=lplan)
        if collect:
            aux = {**aux, **kv}
        return x, aux
    window = cfg.sliding_window if kind == "local_attn" else 0
    return _attn_block_full(cfg, p, x, positions, window=window,
                            collect=collect, lplan=lplan)


# --------------------------------------------------------------------- #
# Full-sequence backbone
# --------------------------------------------------------------------- #

def _embed_in(cfg: ModelConfig, params: Tree, batch: Dict[str, jax.Array],
              ) -> Tuple[jax.Array, jax.Array]:
    """Returns (x [B,S,D], positions)."""
    if "embeds" in batch:
        x = _c(cfg, batch["embeds"])
        b, s = x.shape[:2]
    else:
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = _c(cfg, jnp.take(params["embed"], tokens, axis=0))
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.rope == "mrope":
        positions = batch.get("positions")
        if positions is None:
            base = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            positions = jnp.broadcast_to(base[None], (3, b, s))
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if cfg.rope == "none" and "pos_embed" in params:
        x = x + _c(cfg, params["pos_embed"][:s][None])
    return x, positions


def forward_hidden(params: Tree, cfg: ModelConfig,
                   batch: Dict[str, jax.Array], *,
                   remat: bool = True,
                   act_sharding=None,
                   act_pin_scope: str = "all",
                   plan: Optional[Plan] = None) -> jax.Array:
    """Embedding + all blocks + final norm -> hidden states [B,S,D].

    ``act_sharding``: optional NamedSharding pinning the residual stream
    (§Perf: without a pin, GSPMD is free to shuttle the f32 norm
    intermediates across the model axis — measured as f32 activation
    all-gathers/all-reduces per layer on llama3-8b).  ``act_pin_scope``:
    'all' pins every block boundary, 'embed' only the scan entry.

    ``plan``: a ``core.stream_plan.StreamPlan`` (or None).  When set (or
    when ``cfg.use_fused_kernels`` resolves one), blocks dispatch to the
    fused Pallas kernels the compiler pipeline selected.
    """
    pin_all = act_sharding is not None and act_pin_scope == "all"
    pin = ((lambda a: jax.lax.with_sharding_constraint(a, act_sharding))
           if act_sharding is not None else (lambda a: a))
    pin_block = pin if pin_all else (lambda a: a)
    params = _cast_tree(cfg, params)
    x, positions = _embed_in(cfg, params, batch)
    plan = resolve_plan(cfg, x.shape[0] * x.shape[1], plan=plan)
    x = pin(x)
    period = len(cfg.layer_pattern)
    groups = cfg.num_layers // period
    shared = params.get("shared")

    def group_body(x, block_params: Tuple[Tree, ...]) -> Tuple[jax.Array, None]:
        for pidx in range(period):
            kind = cfg.layer_pattern[pidx]
            x, _ = _apply_block_full(cfg, kind, block_params[pidx], shared,
                                     x, positions, collect=False,
                                     lplan=_lplan(plan, kind))
            x = pin_block(x)
        return x, None

    body = jax.checkpoint(group_body) if remat else group_body
    if groups > 0:
        x, _ = lax.scan(body, x, params["blocks"])
    for i, bp in enumerate(params["rest"]):
        kind = cfg.layer_kind(groups * period + i)
        x, _ = _apply_block_full(cfg, kind, bp, shared, x, positions,
                                 collect=False, lplan=_lplan(plan, kind))
        x = pin_block(x)
    return L.apply_norm(cfg.norm, x, params["final_norm"])


# --------------------------------------------------------------------- #
# Streamed cross-entropy (chunked over sequence)
# --------------------------------------------------------------------- #

def streamed_xent(hidden: jax.Array, head: jax.Array, labels: jax.Array,
                  vocab_size: int, chunk: int = 256) -> jax.Array:
    """Mean CE without materializing [B,S,V] logits.

    hidden: [B,S,D]; head: [D,Vp] (vocab possibly padded); labels: [B,S]
    with -100 = ignore.  Sequence is processed in chunks via ``lax.scan`` —
    the paper's streaming applied to the loss layer.
    """
    b, s, d = hidden.shape
    vp = head.shape[-1]
    c = _chunk_of(s, chunk)
    nc = s // c
    hc = hidden.reshape(b, nc, c, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, c).transpose(1, 0, 2)
    pad_mask = (jnp.arange(vp) >= vocab_size)[None, None]

    def step(carry, inp):
        tot, cnt = carry
        h, y = inp                                    # [B,c,D], [B,c]
        logits = (h @ head).astype(jnp.float32)       # [B,c,Vp]
        logits = jnp.where(pad_mask, -1e30, logits)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(y, 0)[..., None], axis=-1)[..., 0]
        valid = y >= 0
        nll = jnp.where(valid, lse - gold, 0.0)
        return (tot + nll.sum(), cnt + valid.sum()), None

    (tot, cnt), _ = lax.scan(step, (jnp.float32(0.0), jnp.int32(0)), (hc, lc))
    return tot / jnp.maximum(cnt, 1)


def forward_train(params: Tree, cfg: ModelConfig,
                  batch: Dict[str, jax.Array], *,
                  remat: bool = True, act_sharding=None,
                  act_pin_scope: str = "all",
                  plan: Optional[Plan] = None) -> jax.Array:
    """Streamed-CE training loss."""
    labels = batch["labels"]
    plan = resolve_plan(cfg, labels.shape[0] * labels.shape[1], plan=plan)
    hidden = forward_hidden(params, cfg, batch, remat=remat,
                            act_sharding=act_sharding,
                            act_pin_scope=act_pin_scope, plan=plan)
    head = _c(cfg, params["lm_head"])
    if plan is not None and plan.lm_head.fused:
        return L.fused_streamed_xent(hidden, head, labels, cfg.vocab_size,
                                     **plan.lm_head.kw)
    return streamed_xent(hidden, head, labels, cfg.vocab_size)


# --------------------------------------------------------------------- #
# Prefill
# --------------------------------------------------------------------- #

def prefill(params: Tree, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            plan: Optional[Plan] = None) -> Tuple[jax.Array, Tree]:
    """Forward pass that also returns decode caches (sized at the prompt
    length; the serving layer places them into max-length buffers)."""
    params = _cast_tree(cfg, params)
    x, positions = _embed_in(cfg, params, batch)
    plan = resolve_plan(cfg, x.shape[0] * x.shape[1], plan=plan)
    period = len(cfg.layer_pattern)
    groups = cfg.num_layers // period
    shared = params.get("shared")

    def group_body(x, block_params):
        auxes = []
        for pidx in range(period):
            kind = cfg.layer_pattern[pidx]
            x, aux = _apply_block_full(cfg, kind, block_params[pidx], shared,
                                       x, positions, collect=True,
                                       lplan=_lplan(plan, kind))
            auxes.append(aux)
        return x, tuple(auxes)

    caches_rest = []
    if groups > 0:
        x, caches_blocks = lax.scan(group_body, x, params["blocks"])
    else:
        caches_blocks = ()
    for i, bp in enumerate(params["rest"]):
        kind = cfg.layer_kind(groups * period + i)
        x, aux = _apply_block_full(cfg, kind, bp, shared, x, positions,
                                   collect=True, lplan=_lplan(plan, kind))
        caches_rest.append(jax.tree.map(lambda a: a[None], aux))
    x = L.apply_norm(cfg.norm, x, params["final_norm"])
    logits = (x[:, -1:] @ _c(cfg, params["lm_head"])).astype(jnp.float32)
    vp = logits.shape[-1]
    logits = jnp.where((jnp.arange(vp) >= cfg.vocab_size)[None, None],
                       -1e30, logits)
    return logits, {"blocks": caches_blocks, "rest": tuple(caches_rest)}


# --------------------------------------------------------------------- #
# Chunked prefill (fixed-shape tiles against the paged decode cache)
# --------------------------------------------------------------------- #

def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Whether ``prefill_chunk`` can serve this config.

    Chunked prefill carries per-request state between chunks through the
    paged KV pools — which only exists for attention K/V.  SSM / RWKV /
    hybrid stacks carry recurrent state (ssm/conv/wkv/token-shift) that
    the full-sequence mixers cannot yet resume mid-prompt, and mrope's
    3-axis positions are not expressible as a scalar chunk offset; those
    configs prefill whole-prompt (the engine falls back automatically).
    """
    kinds = {cfg.layer_kind(i) for i in range(cfg.num_layers)}
    # cfg.causal is load-bearing: causal masking is what hides the final
    # chunk's zero-pad K/V (kv_len counts pad positions as valid).
    return (cfg.causal and cfg.rope != "mrope"
            and kinds <= {"attn", "local_attn", "global_attn"})


def _attn_block_chunk(cfg: ModelConfig, p: Tree, x: jax.Array, cache: Tree,
                      table_row: jax.Array, chunk_pages: jax.Array,
                      offset: jax.Array, kv_len: jax.Array, *,
                      window: int = 0,
                      lplan: Optional[LPlan] = None,
                      cow_src: Optional[jax.Array] = None,
                      cow_dst: Optional[jax.Array] = None,
                      ) -> Tuple[jax.Array, Tree]:
    """One attention block over a prompt CHUNK, against the paged cache.

    x: [1, C, D]; cache: {"k","v"} pools [P, Hkv, page_size, hd];
    table_row: [max_pages] the slot's logical->physical page map;
    chunk_pages: [C // page_size] physical pages of THIS chunk;
    offset: dynamic chunk start position; kv_len: dynamic valid KV extent
    (= offset + C: earlier chunks plus this one).

    The chunk's K/V are written into their pages FIRST, then attention
    gathers the slot's full page extent and masks by (causal @ absolute
    positions, kv_len) — so queries see chunks 0..k-1 AND their own chunk
    through the same pools the decode step will keep appending to.  Pad
    tokens of a final partial chunk sit at positions past every real
    query, so causal masking excludes them for free.

    ``cow_src``/``cow_dst`` (traced int32 scalars, ``NULL_PAGE`` when
    idle) drive the copy-on-write path: when this chunk's span includes
    a page the slot shares through the prefix cache, the shared page is
    copied onto the private ``cow_dst`` inside both pools before the
    scatter — a shared page is never a write target (DESIGN.md §10).
    ``table_row`` / ``chunk_pages`` already carry ``cow_dst``.
    """
    # Function-local for the same circular-import reason as the decode
    # path: serving imports models at module load.
    from ..serving.kv_cache import (gather_pages, gather_pages_dequant,
                                    live_page_table, place_chunk_pages,
                                    place_chunk_pages_q)
    b, c, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    layout = cfg.kv_cache_layout
    ap = p["attn"]
    q, k, v = _project_qkv(cfg, ap, x, p["ln1"], lplan)
    q = q.reshape(b, c, hq, hd)
    k = k.reshape(b, c, hkv, hd)
    v = v.reshape(b, c, hkv, hd)
    q, k = _qk_normed(cfg, ap, q, k)
    positions = offset + jnp.arange(c)[None]               # [1, C]
    q = L.apply_positional(cfg.rope, q, positions, cfg.rope_theta)
    k = L.apply_positional(cfg.rope, k, positions, cfg.rope_theta)
    k_new = k.transpose(0, 2, 1, 3) if layout == "bhsd" else k
    v_new = v.transpose(0, 2, 1, 3) if layout == "bhsd" else v
    quant = "k_scale" in cache
    if quant:
        kc, ks = place_chunk_pages_q(cache["k"], cache["k_scale"], k_new,
                                     chunk_pages, layout=layout,
                                     cow_src=cow_src, cow_dst=cow_dst)
        vc, vs = place_chunk_pages_q(cache["v"], cache["v_scale"], v_new,
                                     chunk_pages, layout=layout,
                                     cow_src=cow_src, cow_dst=cow_dst)
    else:
        kc = place_chunk_pages(cache["k"], k_new, chunk_pages, layout=layout,
                               cow_src=cow_src, cow_dst=cow_dst)
        vc = place_chunk_pages(cache["v"], v_new, chunk_pages, layout=layout,
                               cow_src=cow_src, cow_dst=cow_dst)
    # Bound KV traffic by the live prefix: the gather touches O(prefix)
    # distinct pages instead of the slot's full table extent (masking at
    # kv_len already discards the dead rows' scores).
    row_live = live_page_table(table_row, kv_len, cache["k"].shape[2])
    choice = lplan.attention if lplan is not None else None
    fused = choice is not None and choice.fused
    if quant and not fused:
        # Eager reference: dense dequantized K/V through the same
        # streaming-attention path the f32 cache takes.
        kseq = gather_pages_dequant(kc, ks, row_live[None], layout=layout)
        vseq = gather_pages_dequant(vc, vs, row_live[None], layout=layout)
    else:
        kseq = gather_pages(kc, row_live[None], layout=layout)
        vseq = gather_pages(vc, row_live[None], layout=layout)
    if layout == "bhsd":
        kseq = kseq.transpose(0, 2, 1, 3)
        vseq = vseq.transpose(0, 2, 1, 3)
    if fused:
        # The plan's flash kernel, offset twin: q_offset/kv_len ride in as
        # scalar-prefetch operands so one compiled program covers every
        # chunk index over any cache fill; the sharded dispatch (and the
        # shard_map it builds) comes from the plan's sharding claim.
        # Quantized: K/V stay codes and the per-page scale rows expand to
        # per-position scale lanes the kernel consumes next to each tile.
        scl = {}
        if quant:
            ps_ = cache["k"].shape[2]
            scl = {"k_scale": jnp.repeat(ks[row_live], ps_, axis=0)[None],
                   "v_scale": jnp.repeat(vs[row_live], ps_, axis=0)[None]}
        o = L.fused_attention_chunk(q, kseq, vseq, offset, kv_len,
                                    causal=cfg.causal, window=window,
                                    **scl, **choice.kw)
    else:
        o = L.streaming_attention(q, kseq, vseq, causal=cfg.causal,
                                  q_offset=offset, window=window,
                                  kv_len=kv_len)
    x = x + o.reshape(b, c, hq * hd) @ ap["wo"]
    x = x + _ffn_block(cfg, p["mlp"], x, p["ln2"], lplan)
    new_kv = {"k": kc, "v": vc}
    if quant:
        new_kv.update(k_scale=ks, v_scale=vs)
    return x, new_kv


def _apply_block_chunk(cfg: ModelConfig, kind: str, p: Tree, x: jax.Array,
                       cache: Tree, table_row: jax.Array,
                       chunk_pages: jax.Array, offset: jax.Array,
                       kv_len: jax.Array,
                       lplan: Optional[LPlan] = None,
                       cow_src: Optional[jax.Array] = None,
                       cow_dst: Optional[jax.Array] = None,
                       ) -> Tuple[jax.Array, Tree]:
    if kind not in ("attn", "local_attn", "global_attn"):
        raise NotImplementedError(
            f"chunked prefill does not support layer kind {kind!r} "
            "(gate on supports_chunked_prefill)")
    window = cfg.sliding_window if kind == "local_attn" else 0
    return _attn_block_chunk(cfg, p, x, cache, table_row, chunk_pages,
                             offset, kv_len, window=window, lplan=lplan,
                             cow_src=cow_src, cow_dst=cow_dst)


def prefill_chunk(params: Tree, cfg: ModelConfig, tokens: jax.Array,
                  cache: Tree, table_row: jax.Array, chunk_pages: jax.Array,
                  offset: jax.Array, last_idx: jax.Array,
                  cow_src: Optional[jax.Array] = None,
                  cow_dst: Optional[jax.Array] = None, *,
                  plan: Optional[Plan] = None,
                  ) -> Tuple[jax.Array, jax.Array, Tree]:
    """Process ONE fixed-size prompt chunk against the paged decode cache.

    tokens: [1, C] int32, the chunk (zero-padded past the prompt's end on
    the final chunk); cache: paged pools from ``serving.kv_cache``
    (donated by the engine — K/V scatters update in place); table_row:
    [max_pages] int32 slot page map; chunk_pages: [C // page_size] int32
    physical pages for this chunk; offset: dynamic chunk start position;
    last_idx: within-chunk index of the prompt's last real token (only
    meaningful on the final chunk — earlier dispatches discard the token).

    ``offset`` may be any page-aligned position, including a NONZERO
    first-dispatch offset against table rows the prefix cache
    pre-populated with shared pages (DESIGN.md §10): the gather walks the
    whole live row, so queries attend to the claimed prefix exactly as
    they would to self-computed chunks.  ``cow_src``/``cow_dst`` (traced
    int32 scalars, ``NULL_PAGE`` when idle) copy one shared page onto a
    private one in every layer's K and V pool before the chunk scatter —
    the copy-on-write step for a chunk whose span overlaps a shared page.

    Every dynamic quantity (offset, last_idx, page ids, the COW pair) is
    a traced operand, so ONE compiled program serves every chunk of every
    prompt — the compile count is independent of the prompt-length mix.
    Returns (next_token [1, 1], logits [1, 1, Vp] at ``last_idx``,
    new_cache).
    """
    if not supports_chunked_prefill(cfg):
        raise NotImplementedError(
            f"chunked prefill unsupported for config {cfg.name!r}")
    params = _cast_tree(cfg, params)
    b, c = tokens.shape
    offset = jnp.asarray(offset, jnp.int32)
    x = _c(cfg, jnp.take(params["embed"], tokens, axis=0))
    x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.rope == "none" and "pos_embed" in params:
        positions = jnp.broadcast_to(offset + jnp.arange(c)[None], (b, c))
        x = x + jnp.take(_c(cfg, params["pos_embed"]), positions, axis=0)
    # Plan keyed on the chunk token count and the gathered cache extent —
    # both static, so the plan (like the program) is one per engine.
    kv_extent = int(table_row.shape[0]) * _cache_page_size(cache)
    plan = resolve_plan(cfg, b * c, kv_len=kv_extent, plan=plan)
    kv_len = offset + c
    period = len(cfg.layer_pattern)
    groups = cfg.num_layers // period

    def group_body(x, inp):
        block_params, cache_g = inp
        new_caches = []
        for pidx in range(period):
            kind = cfg.layer_pattern[pidx]
            x, nc = _apply_block_chunk(cfg, kind, block_params[pidx], x,
                                       cache_g[pidx], table_row,
                                       chunk_pages, offset, kv_len,
                                       lplan=_lplan(plan, kind),
                                       cow_src=cow_src, cow_dst=cow_dst)
            new_caches.append(nc)
        return x, tuple(new_caches)

    if groups > 0:
        x, new_blocks = lax.scan(group_body, x,
                                 (params["blocks"], cache["blocks"]))
    else:
        new_blocks = ()
    new_rest = []
    for i, bp in enumerate(params["rest"]):
        kind = cfg.layer_kind(groups * period + i)
        c_i = jax.tree.map(lambda a: a[0], cache["rest"][i])
        x, nc = _apply_block_chunk(cfg, kind, bp, x, c_i, table_row,
                                   chunk_pages, offset, kv_len,
                                   lplan=_lplan(plan, kind),
                                   cow_src=cow_src, cow_dst=cow_dst)
        new_rest.append(jax.tree.map(lambda a: a[None], nc))
    x = L.apply_norm(cfg.norm, x, params["final_norm"])
    h_last = lax.dynamic_slice_in_dim(x, jnp.asarray(last_idx, jnp.int32),
                                      1, axis=1)            # [1, 1, D]
    logits = (h_last @ _c(cfg, params["lm_head"])).astype(jnp.float32)
    vp = logits.shape[-1]
    logits = jnp.where((jnp.arange(vp) >= cfg.vocab_size)[None, None],
                       -1e30, logits)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return next_tokens, logits, {"blocks": new_blocks,
                                 "rest": tuple(new_rest)}


def _cache_page_size(cache: Tree) -> int:
    """Page size of a paged cache tree (shape[3] of any stacked K/V pool
    leaf [G, P, Hkv, page_size, hd])."""
    from .params import cache_leaf_kind, cache_leaf_name
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if cache_leaf_kind(cache_leaf_name(path)) == "kv":
            return int(leaf.shape[3])
    raise ValueError("cache tree holds no K/V pool leaves")


# --------------------------------------------------------------------- #
# Decode
# --------------------------------------------------------------------- #

def _decode_positions(cache_pos: jax.Array, b: int) -> jax.Array:
    """Normalize a decode write position (scalar or [B]) to a [B] vector —
    per-slot positions are what continuous batching runs on; the scalar
    form is the degenerate all-slots-aligned case."""
    return jnp.broadcast_to(
        jnp.reshape(jnp.asarray(cache_pos, jnp.int32), (-1,)), (b,))


def _attn_block_decode(cfg: ModelConfig, p: Tree, x: jax.Array,
                       cache: Tree, cache_pos: jax.Array,
                       lengths: jax.Array, *, window: int = 0,
                       lplan: Optional[LPlan] = None,
                       page_table: Optional[jax.Array] = None,
                       ) -> Tuple[jax.Array, Tree]:
    """x: [B,1,D]; cache: {"k","v"} [B,Smax,Hkv,hd] contiguous, or paged
    pools [P,Hkv,page_size,hd] when ``page_table`` ([B,max_pages]) is set.

    ``cache_pos`` may be a scalar or a per-slot [B] vector.  With a page
    table the token is scattered through the slot's page indirection and
    attention runs either through the ``paged_attention`` Pallas kernel
    (when the plan selected it) or the gather-pages reference path; the
    contiguous path scatters per slot at its own offset.  The plan's
    flash kernel is never used here — its grid is degenerate at Sq=1.
    """
    b = x.shape[0]
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    layout = cfg.kv_cache_layout
    ap = p["attn"]
    q, k, v = _project_qkv(cfg, ap, x, p["ln1"], lplan)
    q = q.reshape(b, 1, hq, hd)
    k = k.reshape(b, 1, hkv, hd)
    v = v.reshape(b, 1, hkv, hd)
    q, k = _qk_normed(cfg, ap, q, k)
    pos = _decode_positions(cache_pos, b)[:, None]          # [B, 1]
    if cfg.rope == "mrope":
        pos3 = jnp.broadcast_to(pos[None], (3, b, 1))
        q = L.apply_positional(cfg.rope, q, pos3, cfg.rope_theta)
        k = L.apply_positional(cfg.rope, k, pos3, cfg.rope_theta)
    else:
        q = L.apply_positional(cfg.rope, q, pos, cfg.rope_theta)
        k = L.apply_positional(cfg.rope, k, pos, cfg.rope_theta)
    k_new = k.transpose(0, 2, 1, 3) if layout == "bhsd" else k
    v_new = v.transpose(0, 2, 1, 3) if layout == "bhsd" else v
    if page_table is not None:
        # Deliberately deferred: serving imports models at module load, so
        # this back edge to the paged-cache primitives must stay
        # function-local (hoisting it is a circular import).  The
        # primitives are pure array ops; they live in serving because
        # that's where the page allocator that owns their layout lives.
        from ..serving.kv_cache import (gather_pages, gather_pages_dequant,
                                        live_page_table, paged_append,
                                        paged_append_q)
        pos_v = pos[:, 0]
        quant = "k_scale" in cache
        ks = vs = None
        if quant:
            kc, ks = paged_append_q(cache["k"], cache["k_scale"],
                                    page_table, pos_v, k_new, layout=layout)
            vc, vs = paged_append_q(cache["v"], cache["v_scale"],
                                    page_table, pos_v, v_new, layout=layout)
        else:
            kc = paged_append(cache["k"], page_table, pos_v, k_new,
                              layout=layout)
            vc = paged_append(cache["v"], page_table, pos_v, v_new,
                              layout=layout)
        choice = lplan.decode_attn if lplan is not None else None
        if choice is not None and choice.fused:
            o = L.fused_paged_attention(q, kc, vc, page_table, lengths + 1,
                                        window=window, k_scale=ks,
                                        v_scale=vs, shard=choice.sharding)
        else:
            # Bound the gather by each slot's live prefix, mirroring the
            # chunk path (the length mask already discards dead rows).
            tbl_live = live_page_table(page_table, lengths + 1,
                                       cache["k"].shape[2])
            if quant:
                kd = gather_pages_dequant(kc, ks, tbl_live, layout=layout)
                vd = gather_pages_dequant(vc, vs, tbl_live, layout=layout)
            else:
                kd = gather_pages(kc, tbl_live, layout=layout)
                vd = gather_pages(vc, tbl_live, layout=layout)
            o = L.decode_attention(q, kd, vd, lengths + 1, window=window,
                                   layout=layout)
    else:
        from .params import kv_seq_axis
        ax = kv_seq_axis(layout)
        seq_len = cache["k"].shape[ax]
        # Per-slot scatter (a slot at capacity rewrites its final row; the
        # engine retires it there), vmapped so each slot writes its own
        # offset — the wave-shared scalar position is just the aligned case.
        pos_w = jnp.minimum(pos[:, 0], seq_len - 1)

        def upd(c, new, p_):
            return lax.dynamic_update_slice_in_dim(
                c, new.astype(c.dtype), p_, axis=ax)

        kc = jax.vmap(upd)(cache["k"], k_new, pos_w)
        vc = jax.vmap(upd)(cache["v"], v_new, pos_w)
        o = L.decode_attention(q, kc, vc, lengths + 1, window=window,
                               layout=layout)
    x = x + o.reshape(b, 1, hq * hd) @ ap["wo"]
    x = x + _ffn_block(cfg, p["mlp"], x, p["ln2"], lplan)
    new_kv = {"k": kc, "v": vc}
    if page_table is not None and "k_scale" in cache:
        new_kv.update(k_scale=ks, v_scale=vs)
    return x, new_kv


def _mamba_block_decode(cfg: ModelConfig, p: Tree, x: jax.Array,
                        cache: Tree) -> Tuple[jax.Array, Tree]:
    b = x.shape[0]
    m = p["mamba"]
    h = L.apply_norm(cfg.norm, x, p["ln"])[:, 0]           # [B,D]
    xin = h @ m["wx"]
    z = h @ m["wz"]
    bmat = h @ m["wb"]
    cmat = h @ m["wc"]
    dt = jax.nn.softplus(h @ m["wdt"] + m["dt_bias"].astype(h.dtype))
    # Conv state update: cache["conv"] holds the previous K-1 inputs.
    conv_in = jnp.concatenate([cache["conv"],
                               xin[:, None].astype(cache["conv"].dtype)],
                              axis=1)                      # [B,K,di]
    w = m["conv_w"]
    y = jnp.einsum("bkd,kd->bd", conv_in.astype(jnp.float32),
                   w.astype(jnp.float32))
    xconv = jax.nn.silu(y + m["conv_b"].astype(jnp.float32)).astype(x.dtype)
    hps = xconv.reshape(b, cfg.ssm_heads, cfg.ssm_head_dim)
    yssm, state = L.mamba2_decode_step(hps, dt, m["a_log"], bmat, cmat,
                                       m["d_skip"], cache["ssm"])
    yin = yssm.reshape(b, cfg.d_inner) * jax.nn.silu(z)
    x = x + (yin @ m["wout"])[:, None]
    return x, {"ssm": state, "conv": conv_in[:, 1:]}


def _rwkv_block_decode(cfg: ModelConfig, p: Tree, x: jax.Array,
                       cache: Tree) -> Tuple[jax.Array, Tree]:
    b = x.shape[0]
    h, n, d = cfg.rwkv_heads, cfg.rwkv_head_dim, cfg.d_model
    tm, cm = p["tm"], p["cm"]
    xa = L.apply_norm(cfg.norm, x, p["ln1"])[:, 0]
    xs = cache["tm_shift"].astype(xa.dtype)

    def mix(name):
        mu = tm[f"mix_{name}"].astype(xa.dtype)
        return xa * mu + xs * (1.0 - mu)

    r = (mix("r") @ tm["wr"]).reshape(b, 1, h, n)
    k = (mix("k") @ tm["wk"]).reshape(b, 1, h, n)
    v = (mix("v") @ tm["wv"]).reshape(b, 1, h, n)
    g = jax.nn.silu(mix("g") @ tm["wg"])
    wdec = jnp.exp(-jnp.exp(
        (mix("w") @ tm["ww"]).astype(jnp.float32)
        + tm["w_bias"].reshape(1, h * n))).reshape(b, 1, h, n)
    y, state = L.wkv6(r, k, v, wdec, tm["u"],
                      init_state=cache["wkv"])
    y = (y.reshape(b, d) * g) @ tm["wo"]
    x = x + y[:, None]
    xc = L.apply_norm(cfg.norm, x, p["ln2"])[:, 0]
    xcs = cache["cm_shift"].astype(xc.dtype)

    def cmix(name):
        mu = cm[f"mix_{name}"].astype(xc.dtype)
        return xc * mu + xcs * (1.0 - mu)

    kk = jnp.square(jax.nn.relu(cmix("k") @ cm["wk"]))
    rr = jax.nn.sigmoid(cmix("r") @ cm["wr"])
    x = x + (rr * (kk @ cm["wv"]))[:, None]
    new = {"wkv": state, "tm_shift": xa.astype(cache["tm_shift"].dtype),
           "cm_shift": xc.astype(cache["cm_shift"].dtype)}
    return x, new


def _apply_block_decode(cfg: ModelConfig, kind: str, p: Tree, shared: Tree,
                        x: jax.Array, cache: Tree, cache_pos: jax.Array,
                        lengths: jax.Array,
                        lplan: Optional[LPlan] = None,
                        page_table: Optional[jax.Array] = None,
                        ) -> Tuple[jax.Array, Tree]:
    if kind == "rwkv":
        return _rwkv_block_decode(cfg, p, x, cache)
    if kind == "mamba":
        return _mamba_block_decode(cfg, p, x, cache)
    if kind == "mamba+shared_attn":
        mamba_cache = {"ssm": cache["ssm"], "conv": cache["conv"]}
        attn_cache = {n: cache[n] for n in ("k", "v", "k_scale", "v_scale")
                      if n in cache}
        x, nm = _mamba_block_decode(cfg, p, x, mamba_cache)
        x, na = _attn_block_decode(cfg, shared, x, attn_cache, cache_pos,
                                   lengths, lplan=lplan,
                                   page_table=page_table)
        return x, {**nm, **na}
    window = cfg.sliding_window if kind == "local_attn" else 0
    return _attn_block_decode(cfg, p, x, cache, cache_pos, lengths,
                              window=window, lplan=lplan,
                              page_table=page_table)


def decode_step(params: Tree, cfg: ModelConfig, tokens: jax.Array,
                cache: Tree, cache_pos: jax.Array, lengths: jax.Array, *,
                page_table: Optional[jax.Array] = None,
                plan: Optional[Plan] = None,
                ) -> Tuple[jax.Array, jax.Array, Tree]:
    """One decoding step.

    tokens: [B,1] int32; cache: pytree from ``init_cache``/``prefill`` (or
    paged pools from ``serving.kv_cache`` when ``page_table`` is given);
    cache_pos: int32 write position, scalar or per-slot [B]; lengths: [B]
    valid lengths; page_table: [B, max_pages] int32 page indirection.
    Returns (next_tokens [B,1], logits [B,1,Vp], new_cache).
    """
    params = _cast_tree(cfg, params)
    b = tokens.shape[0]
    pos_v = _decode_positions(cache_pos, b)
    x = _c(cfg, jnp.take(params["embed"], tokens, axis=0))
    x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.rope == "none" and "pos_embed" in params:
        x = x + jnp.take(_c(cfg, params["pos_embed"]), pos_v,
                         axis=0)[:, None]
    plan = resolve_plan(cfg, b,
                        kv_len=_cache_kv_len(cfg, cache, page_table),
                        plan=plan)
    period = len(cfg.layer_pattern)
    groups = cfg.num_layers // period
    shared = params.get("shared")

    def group_body(x, inp):
        block_params, cache_g = inp
        new_caches = []
        for pidx in range(period):
            kind = cfg.layer_pattern[pidx]
            x, nc = _apply_block_decode(cfg, kind, block_params[pidx],
                                        shared, x, cache_g[pidx], pos_v,
                                        lengths, lplan=_lplan(plan, kind),
                                        page_table=page_table)
            new_caches.append(nc)
        return x, tuple(new_caches)

    if groups > 0:
        x, new_blocks = lax.scan(group_body, x,
                                 (params["blocks"], cache["blocks"]))
    else:
        new_blocks = ()
    new_rest = []
    for i, bp in enumerate(params["rest"]):
        kind = cfg.layer_kind(groups * period + i)
        c_i = jax.tree.map(lambda a: a[0], cache["rest"][i])
        x, nc = _apply_block_decode(cfg, kind, bp, shared, x, c_i,
                                    pos_v, lengths,
                                    lplan=_lplan(plan, kind),
                                    page_table=page_table)
        new_rest.append(jax.tree.map(lambda a: a[None], nc))
    x = L.apply_norm(cfg.norm, x, params["final_norm"])
    logits = (x @ _c(cfg, params["lm_head"])).astype(jnp.float32)
    vp = logits.shape[-1]
    logits = jnp.where((jnp.arange(vp) >= cfg.vocab_size)[None, None],
                       -1e30, logits)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    new_cache = {"blocks": new_blocks, "rest": tuple(new_rest)}
    return next_tokens, logits, new_cache


# --------------------------------------------------------------------- #
# Speculative verify (draft-then-verify decode, DESIGN.md §11)
# --------------------------------------------------------------------- #

def supports_speculative(cfg: ModelConfig) -> bool:
    """Whether ``verify_step`` can serve this config.

    Speculative decode needs a rejected draft to be UNDOABLE: for paged
    attention K/V that is a page-table edit (``rollback_extent``), but
    SSM / conv / RWKV recurrent state folds every consumed token into a
    dense carry that cannot be truncated, so hybrid stacks are out.  The
    remaining constraints are the chunked-prefill ones: causal masking is
    what scopes each window row to its own prefix, and mrope's 3-axis
    positions don't extend along a scalar window offset.
    """
    return supports_chunked_prefill(cfg)


def _attn_block_verify(cfg: ModelConfig, p: Tree, x: jax.Array,
                       cache: Tree, cache_pos: jax.Array,
                       lengths: jax.Array, *, window: int = 0,
                       lplan: Optional[LPlan] = None,
                       page_table: Optional[jax.Array] = None,
                       ) -> Tuple[jax.Array, Tree]:
    """One attention block over a W-token verify window, paged cache only.

    x: [B, W, D] — the pending token plus W-1 draft candidates per slot;
    ``cache_pos`` ([B] or scalar) is the window's first write position,
    so K/V rows land at ``pos .. pos + W - 1`` and window row i attends
    through position ``pos + i`` (its own token included), exactly the
    extent single-token decode would see after consuming i accepted
    tokens.  Rows past the accepted prefix leave stale K/V behind; the
    engine truncates them via ``rollback_extent`` and the NEXT dispatch
    overwrites them — in between they sit beyond every slot's length and
    are therefore invisible to the masks.
    """
    if page_table is None:
        raise NotImplementedError(
            "verify_step requires the paged KV cache (rollback is a "
            "page-table edit; the contiguous cache has no equivalent)")
    from ..serving.kv_cache import (gather_pages, gather_pages_dequant,
                                    live_page_table, paged_append_window,
                                    paged_append_window_q)
    b, w, _ = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    layout = cfg.kv_cache_layout
    ap = p["attn"]
    q, k, v = _project_qkv(cfg, ap, x, p["ln1"], lplan)
    q = q.reshape(b, w, hq, hd)
    k = k.reshape(b, w, hkv, hd)
    v = v.reshape(b, w, hkv, hd)
    q, k = _qk_normed(cfg, ap, q, k)
    pos0 = _decode_positions(cache_pos, b)
    pos = pos0[:, None] + jnp.arange(w)[None]               # [B, W]
    q = L.apply_positional(cfg.rope, q, pos, cfg.rope_theta)
    k = L.apply_positional(cfg.rope, k, pos, cfg.rope_theta)
    k_new = k.transpose(0, 2, 1, 3) if layout == "bhsd" else k
    v_new = v.transpose(0, 2, 1, 3) if layout == "bhsd" else v
    quant = "k_scale" in cache
    ks = vs = None
    if quant:
        kc, ks = paged_append_window_q(cache["k"], cache["k_scale"],
                                       page_table, pos0, k_new,
                                       layout=layout)
        vc, vs = paged_append_window_q(cache["v"], cache["v_scale"],
                                       page_table, pos0, v_new,
                                       layout=layout)
    else:
        kc = paged_append_window(cache["k"], page_table, pos0, k_new,
                                 layout=layout)
        vc = paged_append_window(cache["v"], page_table, pos0, v_new,
                                 layout=layout)
    choice = lplan.verify_attn if lplan is not None else None
    if choice is not None and choice.fused:
        o = L.fused_verify_attention(q, kc, vc, page_table, lengths,
                                     window=window, k_scale=ks, v_scale=vs,
                                     shard=choice.sharding)
    else:
        tbl_live = live_page_table(page_table, lengths + w,
                                   cache["k"].shape[2])
        if quant:
            kd = gather_pages_dequant(kc, ks, tbl_live, layout=layout)
            vd = gather_pages_dequant(vc, vs, tbl_live, layout=layout)
        else:
            kd = gather_pages(kc, tbl_live, layout=layout)
            vd = gather_pages(vc, tbl_live, layout=layout)
        o = L.verify_attention(q, kd, vd, lengths, window=window,
                               layout=layout)
    x = x + o.reshape(b, w, hq * hd) @ ap["wo"]
    x = x + _ffn_block(cfg, p["mlp"], x, p["ln2"], lplan)
    new_kv = {"k": kc, "v": vc}
    if quant:
        new_kv.update(k_scale=ks, v_scale=vs)
    return x, new_kv


def _apply_block_verify(cfg: ModelConfig, kind: str, p: Tree, x: jax.Array,
                        cache: Tree, cache_pos: jax.Array,
                        lengths: jax.Array,
                        lplan: Optional[LPlan] = None,
                        page_table: Optional[jax.Array] = None,
                        ) -> Tuple[jax.Array, Tree]:
    if kind not in ("attn", "local_attn", "global_attn"):
        raise NotImplementedError(
            f"speculative verify does not support layer kind {kind!r} "
            "(gate on supports_speculative)")
    window = cfg.sliding_window if kind == "local_attn" else 0
    return _attn_block_verify(cfg, p, x, cache, cache_pos, lengths,
                              window=window, lplan=lplan,
                              page_table=page_table)


def verify_step(params: Tree, cfg: ModelConfig, tokens: jax.Array,
                cache: Tree, cache_pos: jax.Array, lengths: jax.Array, *,
                page_table: jax.Array,
                plan: Optional[Plan] = None,
                ) -> Tuple[jax.Array, jax.Array, Tree]:
    """Score a W-token speculative window in ONE dispatch.

    tokens: [B, W] int32 — column 0 the pending (already-committed) input
    token, columns 1..W-1 the draft candidates; cache: paged pools;
    cache_pos: window start write position ([B] or scalar); lengths: [B]
    tokens already in the cache (== cache_pos on the serving path);
    page_table: [B, max_pages].  Returns (greedy [B, W], logits
    [B, W, Vp], new_cache): ``greedy[:, i]`` is the model's next token
    after consuming ``tokens[:, :i+1]`` — the engine accepts draft
    ``tokens[:, i]`` while it equals ``greedy[:, i-1]``, and every
    accepted row's logits are the ones non-speculative decode would have
    produced (the verify attention scopes row i to its own causal
    prefix).  Sits between ``prefill_chunk`` and ``decode_step``: same
    paged cache, same dynamic per-slot operands, one compiled program
    per window size W.
    """
    if not supports_speculative(cfg):
        raise NotImplementedError(
            f"speculative verify unsupported for config {cfg.name!r}")
    params = _cast_tree(cfg, params)
    b, w = tokens.shape
    pos_v = _decode_positions(cache_pos, b)
    x = _c(cfg, jnp.take(params["embed"], tokens, axis=0))
    x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.rope == "none" and "pos_embed" in params:
        pos = pos_v[:, None] + jnp.arange(w)[None]
        x = x + jnp.take(_c(cfg, params["pos_embed"]), pos, axis=0)
    plan = resolve_plan(cfg, b * w,
                        kv_len=_cache_kv_len(cfg, cache, page_table),
                        plan=plan)
    period = len(cfg.layer_pattern)
    groups = cfg.num_layers // period

    def group_body(x, inp):
        block_params, cache_g = inp
        new_caches = []
        for pidx in range(period):
            kind = cfg.layer_pattern[pidx]
            x, nc = _apply_block_verify(cfg, kind, block_params[pidx], x,
                                        cache_g[pidx], pos_v, lengths,
                                        lplan=_lplan(plan, kind),
                                        page_table=page_table)
            new_caches.append(nc)
        return x, tuple(new_caches)

    if groups > 0:
        x, new_blocks = lax.scan(group_body, x,
                                 (params["blocks"], cache["blocks"]))
    else:
        new_blocks = ()
    new_rest = []
    for i, bp in enumerate(params["rest"]):
        kind = cfg.layer_kind(groups * period + i)
        c_i = jax.tree.map(lambda a: a[0], cache["rest"][i])
        x, nc = _apply_block_verify(cfg, kind, bp, x, c_i, pos_v, lengths,
                                    lplan=_lplan(plan, kind),
                                    page_table=page_table)
        new_rest.append(jax.tree.map(lambda a: a[None], nc))
    x = L.apply_norm(cfg.norm, x, params["final_norm"])
    logits = (x @ _c(cfg, params["lm_head"])).astype(jnp.float32)
    vp = logits.shape[-1]
    logits = jnp.where((jnp.arange(vp) >= cfg.vocab_size)[None, None],
                       -1e30, logits)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    new_cache = {"blocks": new_blocks, "rest": tuple(new_rest)}
    return greedy, logits, new_cache
