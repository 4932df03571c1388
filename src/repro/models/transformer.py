"""Decoder-only LM: config, init, sharded forward/loss, prefill + decode.

Distribution (DESIGN.md §4):
  * params: Megatron tensor parallelism over the `model` axis (QKV/in-proj
    column-sharded, O/out-proj row-sharded, vocab sharded on embed + head);
    MoE experts sharded over `model` (see models/moe.py).
  * activations: batch over ("pod","data"), TP dims over "model",
    enforced with with_sharding_constraint.
  * embedding lookup: explicit Megatron vocab-parallel gather + psum under
    shard_map (GSPMD's default gather strategy may replicate a multi-GB
    embedding -- we do not let it).
  * layers run under lax.scan with configurable remat; the logits/loss is
    scanned over sequence chunks so the (B, S, V) tensor never materializes.
  * decode: KV cache either head-sharded (kv_heads % tp == 0, zero-comm) or
    sequence-sharded with the distributed flash-decode LSE merge
    (models/attention.py) -- required for danube (kv=8 < tp=16) and for
    long_500k where the cache must spread over every chip.
  * sliding-window models (danube) use a ring-buffer KV cache of size
    `window`: decode at 500k context touches 4096 positions, not 524288.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .attention import (causal_attention, decode_attention_local,
                        decode_attention_seqsharded, shard_lengths)
from .layers import (apply_norm, apply_rope, constrain, dense_init,
                     embed_init, gated_mlp, norm_param, softmax_xent_chunked)
from .moe import MoEConfig, moe_ffn


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    norm: str = "rmsnorm"            # rmsnorm | rmsnorm_gemma | nonparam_ln
    activation: str = "silu"         # silu (SwiGLU) | gelu_tanh (GeGLU)
    sliding_window: Optional[int] = None
    rope_theta: float = 10000.0
    moe: Optional[MoEConfig] = None
    tie_embeddings: bool = False
    embed_scale: bool = False        # gemma: x *= sqrt(d_model)
    dtype: str = "bfloat16"
    remat: str = "full"              # none | full | dots
    loss_chunks: int = 8
    attn_chunk_q: int = 512
    attn_chunk_kv: int = 1024

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.d_head

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.d_head

    def n_params(self) -> int:
        """Total parameter count (dense equivalent; MoE counts all experts)."""
        d, v, l = self.d_model, self.vocab, self.n_layers
        attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        if self.moe:
            m = self.moe
            ffn = (d * m.n_experts  # router
                   + m.n_experts * 3 * d * m.d_ff_expert
                   + (3 * d * m.d_ff_shared if m.n_shared else 0))
        else:
            ffn = 3 * d * self.d_ff
        emb = v * d * (1 if self.tie_embeddings else 2)
        return l * (attn + ffn) + emb

    def n_active_params(self) -> int:
        """Params touched per token (MoE: top-k + shared only)."""
        if not self.moe:
            return self.n_params()
        d, v, l, m = self.d_model, self.vocab, self.n_layers, self.moe
        attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        ffn = (d * m.n_experts + m.top_k * 3 * d * m.d_ff_expert
               + (3 * d * m.d_ff_shared if m.n_shared else 0))
        emb = v * d * (1 if self.tie_embeddings else 2)
        return l * (attn + ffn) + emb


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh context handed to model code; None mesh = unsharded smoke path."""
    mesh: Optional[Mesh] = None
    model_axis: Optional[str] = "model"

    @property
    def batch_axes(self) -> tuple:
        if self.mesh is None:
            return ()
        names = self.mesh.axis_names
        return tuple(a for a in ("pod", "data") if a in names)

    @property
    def tp(self) -> int:
        if self.mesh is None or self.model_axis not in self.mesh.axis_names:
            return 1
        return self.mesh.devices.shape[self.mesh.axis_names.index(self.model_axis)]

    def spec(self, *dims) -> Optional[P]:
        if self.mesh is None:
            return None
        return P(*dims)

    def batch_spec(self, *rest) -> Optional[P]:
        if self.mesh is None:
            return None
        ba = self.batch_axes
        return P(ba if ba else None, *rest)

    def axis_prod(self, axes) -> int:
        if axes is None:
            return 1
        axes = (axes,) if isinstance(axes, str) else axes
        n = 1
        for a in axes:
            if a in self.mesh.axis_names:
                n *= self.mesh.devices.shape[self.mesh.axis_names.index(a)]
        return n

    def sanitize(self, spec: Optional[P], shape) -> Optional[P]:
        """Drop sharding on any dim whose size is not divisible by its mesh
        axes (batch=1 serving cells, tiny decode token counts, ...)."""
        if self.mesh is None or spec is None:
            return spec
        dims = list(spec) + [None] * (len(shape) - len(spec))
        out = []
        for d, s in zip(dims, shape):
            out.append(d if d is None or (s >= self.axis_prod(d)
                                          and s % self.axis_prod(d) == 0)
                       else None)
        return P(*out)

    def constrain(self, x, spec: Optional[P]):
        """with_sharding_constraint with an explicit NamedSharding (works
        without any ambient mesh context; no-op when unsharded).  Specs are
        sanitized against the array shape."""
        if self.mesh is None or spec is None:
            return x
        from jax.sharding import NamedSharding
        spec = self.sanitize(spec, x.shape)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))


# ---------------------------------------------------------------------------
# Init + param specs
# ---------------------------------------------------------------------------
def init_lm_params(cfg: LMConfig, key: jax.Array) -> dict:
    keys = jax.random.split(key, 16)
    d, l = cfg.d_model, cfg.n_layers

    def stack(fn, key, *shape_args):
        ks = jax.random.split(key, l)
        return jnp.stack([fn(ks[i], *shape_args) for i in range(l)])

    layers: dict[str, Any] = {
        "attn_norm": _stack_norm(cfg, l),
        "mlp_norm": _stack_norm(cfg, l),
        "wq": stack(dense_init, keys[0], d, cfg.q_dim),
        "wk": stack(dense_init, keys[1], d, cfg.kv_dim),
        "wv": stack(dense_init, keys[2], d, cfg.kv_dim),
        "wo": stack(dense_init, keys[3], cfg.q_dim, d),
    }
    if cfg.moe:
        m = cfg.moe
        e = m.n_experts_padded
        def estack(key, d_in, d_out):
            ks = jax.random.split(key, l)
            return jnp.stack([
                jnp.stack([dense_init(k2, d_in, d_out)
                           for k2 in jax.random.split(ks[i], e)])
                for i in range(l)])
        layers["router"] = stack(dense_init, keys[4], d, m.n_experts)
        layers["we_gate"] = estack(keys[5], d, m.d_ff_expert)
        layers["we_in"] = estack(keys[6], d, m.d_ff_expert)
        layers["we_out"] = estack(keys[7], m.d_ff_expert, d)
        if m.n_shared:
            layers["ws_gate"] = stack(dense_init, keys[8], d, m.d_ff_shared)
            layers["ws_in"] = stack(dense_init, keys[9], d, m.d_ff_shared)
            layers["ws_out"] = stack(dense_init, keys[10], m.d_ff_shared, d)
    else:
        layers["w_gate"] = stack(dense_init, keys[5], d, cfg.d_ff)
        layers["w_in"] = stack(dense_init, keys[6], d, cfg.d_ff)
        layers["w_out"] = stack(dense_init, keys[7], cfg.d_ff, d)

    params = {
        "embed": embed_init(keys[11], cfg.vocab, d),
        "final_norm": norm_param(cfg.norm, d),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[12], d, cfg.vocab)
    return params


def _stack_norm(cfg: LMConfig, l: int):
    p = norm_param(cfg.norm, cfg.d_model)
    return None if p is None else jnp.stack([p] * l)


def lm_param_specs(cfg: LMConfig, ctx: ShardCtx,
                   fsdp_axis: Optional[str] = None) -> dict:
    """PartitionSpec tree matching init_lm_params output.

    fsdp_axis (training): additionally shard every weight over that axis on
    its first free divisible dim -- 2D (FSDP x TP) parameter layout.  GSPMD
    then all-gathers each layer's slice inside the scan (forward) and
    reduce-scatters its gradient (backward), and the AdamW state inherits
    the fully-sharded layout (ZeRO-3-style memory: params+moments / N_mesh).
    """
    if ctx.mesh is None:
        return jax.tree.map(lambda _: None, jax.eval_shape(
            lambda: init_lm_params(cfg, jax.random.PRNGKey(0))))
    mdl = ctx.model_axis
    layers: dict[str, Any] = {
        "attn_norm": None if cfg.norm == "nonparam_ln" else P(None, None),
        "mlp_norm": None if cfg.norm == "nonparam_ln" else P(None, None),
        "wq": P(None, None, mdl),
        "wk": P(None, None, mdl),
        "wv": P(None, None, mdl),
        "wo": P(None, mdl, None),
    }
    if cfg.moe:
        layers["router"] = P(None, None, None)
        layers["we_gate"] = P(None, mdl, None, None)
        layers["we_in"] = P(None, mdl, None, None)
        layers["we_out"] = P(None, mdl, None, None)
        if cfg.moe.n_shared:
            layers["ws_gate"] = P(None, None, mdl)
            layers["ws_in"] = P(None, None, mdl)
            layers["ws_out"] = P(None, mdl, None)
    else:
        layers["w_gate"] = P(None, None, mdl)
        layers["w_in"] = P(None, None, mdl)
        layers["w_out"] = P(None, mdl, None)
    specs = {
        "embed": P(mdl, None),
        "final_norm": None if cfg.norm == "nonparam_ln" else P(None),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, mdl)
    if fsdp_axis is not None:
        shapes = jax.eval_shape(
            lambda: init_lm_params(cfg, jax.random.PRNGKey(0)))
        ax_size = ctx.axis_prod(fsdp_axis)

        def add_fsdp(spec, shaped):
            if spec is None or shaped.ndim < 2:
                return spec
            dims = list(spec) + [None] * (shaped.ndim - len(spec))
            for i, d in enumerate(dims):
                if d is None and shaped.shape[i] % ax_size == 0 \
                        and shaped.shape[i] >= ax_size:
                    dims[i] = fsdp_axis
                    return P(*dims)
            return spec

        specs = jax.tree.map(add_fsdp, specs, shapes,
                             is_leaf=lambda x: x is None or isinstance(x, P))
    return specs


# ---------------------------------------------------------------------------
# Embedding (vocab-parallel)
# ---------------------------------------------------------------------------
def embed_lookup(embed: jnp.ndarray, tokens: jnp.ndarray, cfg: LMConfig,
                 ctx: ShardCtx) -> jnp.ndarray:
    """(V, d) x (B, S) -> (B, S, d); Megatron vocab-parallel under shard_map."""
    if ctx.mesh is None or ctx.tp == 1:
        out = embed[tokens]
    else:
        from jax.experimental.shard_map import shard_map
        mdl = ctx.model_axis
        v_local = cfg.vocab // ctx.tp

        def body(emb_l, tok):
            off = jax.lax.axis_index(mdl) * v_local
            loc = tok.astype(jnp.int32) - off
            ok = (loc >= 0) & (loc < v_local)
            rows = emb_l[jnp.clip(loc, 0, v_local - 1)]
            rows = jnp.where(ok[..., None], rows, 0.0)
            return jax.lax.psum(rows, mdl)

        tok_spec = ctx.sanitize(ctx.batch_spec(None), tokens.shape)
        out_spec = P(*(list(tok_spec) + [None]))
        out = shard_map(body, mesh=ctx.mesh,
                        in_specs=(P(mdl, None), tok_spec),
                        out_specs=out_spec,
                        check_rep=False)(embed, tokens)
    out = out.astype(cfg.compute_dtype)
    if cfg.embed_scale:
        out = out * jnp.asarray(cfg.d_model ** 0.5, cfg.compute_dtype)
    return out


# ---------------------------------------------------------------------------
# One transformer layer (shared by train / prefill / decode)
# ---------------------------------------------------------------------------
def _attn_qkv(x, lp, cfg: LMConfig, ctx: ShardCtx, positions):
    b, s, _ = x.shape
    h = apply_norm(cfg.norm, x, lp["attn_norm"])
    q = ctx.constrain(h @ lp["wq"].astype(h.dtype), ctx.batch_spec(None, ctx.model_axis))
    k = ctx.constrain(h @ lp["wk"].astype(h.dtype), ctx.batch_spec(None, ctx.model_axis))
    v = ctx.constrain(h @ lp["wv"].astype(h.dtype), ctx.batch_spec(None, ctx.model_axis))
    q = q.reshape(b, s, cfg.n_heads, cfg.d_head)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _ffn(x, lp, cfg: LMConfig, ctx: ShardCtx):
    h = apply_norm(cfg.norm, x, lp["mlp_norm"])
    if cfg.moe:
        out, aux = moe_ffn(h, lp, cfg.moe, mesh=ctx.mesh,
                           batch_axes=ctx.batch_axes or None,
                           model_axis=ctx.model_axis if ctx.tp > 1 else None,
                           activation=cfg.activation)
        return out, aux
    hidden_spec = ctx.batch_spec(None, ctx.model_axis)
    g = ctx.constrain(h @ lp["w_gate"].astype(h.dtype), hidden_spec)
    i = ctx.constrain(h @ lp["w_in"].astype(h.dtype), hidden_spec)
    from .layers import act_fn
    out = (act_fn(cfg.activation)(g) * i) @ lp["w_out"].astype(h.dtype)
    return out, jnp.float32(0.0)


def layer_forward(x, lp, cfg: LMConfig, ctx: ShardCtx, positions):
    """Full-sequence layer (train / prefill). Returns (x, aux, (k, v))."""
    q, k, v = _attn_qkv(x, lp, cfg, ctx, positions)
    att = causal_attention(q, k, v, q_offset=0, window=cfg.sliding_window,
                           chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv)
    b, s, _, _ = att.shape
    att = att.reshape(b, s, cfg.q_dim)
    x = x + ctx.constrain(att @ lp["wo"].astype(att.dtype),
                          ctx.batch_spec(None, None))
    ffn_out, aux = _ffn(x, lp, cfg, ctx)
    x = x + ffn_out
    x = ctx.constrain(x, ctx.batch_spec(None, None))
    return x, aux, (k, v)


def _remat_wrap(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Forward / loss (training)
# ---------------------------------------------------------------------------
def compute_cast(tree, dtype):
    """Cast float params to the compute dtype *before* the layer scan: the
    FSDP all-gathers that XLA hoists out of the loop then move bf16, not
    f32 (measured 12.4 -> 3.1 GiB on moonshot train), and it is standard
    mixed precision (f32 master weights live only in the optimizer)."""
    return jax.tree.map(
        lambda a: a.astype(dtype)
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)


def _layer_scan(params_layers, x, cfg: LMConfig, step_fn):
    """Scan over layers with the configured remat strategy.

    remat="2level": sqrt-remat -- layers regrouped (outer, inner); only the
    outer carries are saved (outer count ~ sqrt(L)), the inner scan is
    recomputed inside each outer backward step.  Cuts the saved-activation
    stack from L to outer+inner carries.
    """
    if cfg.remat == "2level":
        l = cfg.n_layers
        outer = max(f for f in range(1, int(l ** 0.5) + 1) if l % f == 0)
        inner = l // outer
        grouped = jax.tree.map(
            lambda a: a.reshape((outer, inner) + a.shape[1:]), params_layers)

        def outer_body(carry, lp_group):
            def inner_body(c, lp):
                return step_fn(c, lp), None
            c, _ = jax.lax.scan(inner_body, carry, lp_group)
            return c, None

        return jax.lax.scan(jax.checkpoint(outer_body), x, grouped)[0]
    body = _remat_wrap(lambda c, lp: (step_fn(c, lp), None), cfg.remat)
    return jax.lax.scan(body, x, params_layers)[0]


def forward_hidden(params, cfg: LMConfig, tokens, ctx: ShardCtx):
    """tokens (B, S) -> final hidden (B, S, d) + summed moe aux loss."""
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens, cfg, ctx)
    x = ctx.constrain(x, ctx.batch_spec(None, None))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def step(carry, lp):
        x, aux = carry
        x, a, _ = layer_forward(x, lp, cfg, ctx, positions)
        return (x, aux + a)

    layers_c = compute_cast(params["layers"], cfg.compute_dtype)
    x, aux = _layer_scan(layers_c, (x, jnp.float32(0.0)), cfg, step)
    x = apply_norm(cfg.norm, x, params["final_norm"])
    return x, aux


def lm_head_logits(params, cfg: LMConfig, x, ctx: ShardCtx):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ w.astype(x.dtype)
    return ctx.constrain(logits, ctx.batch_spec(None, ctx.model_axis))


def lm_loss(params, cfg: LMConfig, tokens, labels, ctx: ShardCtx):
    """Mean next-token cross entropy (+ MoE aux). tokens/labels (B, S)."""
    x, aux = forward_hidden(params, cfg, tokens, ctx)
    ce = softmax_xent_chunked(
        lambda xc: lm_head_logits(params, cfg, xc, ctx),
        x, labels, n_chunks=min(cfg.loss_chunks, x.shape[1]))
    return ce + aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------
def cache_len_for(cfg: LMConfig, seq_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(seq_len, cfg.sliding_window)
    return seq_len


def init_cache(cfg: LMConfig, batch: int, seq_len: int, dtype=None):
    """(k, v) caches (L, B, S_c, Hkv, Dh) + lengths (B,)."""
    sc = cache_len_for(cfg, seq_len)
    dtype = dtype or cfg.compute_dtype
    shape = (cfg.n_layers, batch, sc, cfg.n_kv_heads, cfg.d_head)
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
            jnp.zeros((batch,), jnp.int32))


def cache_specs(cfg: LMConfig, ctx: ShardCtx, mode: str):
    """PartitionSpecs for (cache_k, cache_v, lengths).

    mode: "head" -- kv heads over model (requires divisibility);
          "seq"  -- cache sequence over model;
          "seq_all" -- cache sequence over every mesh axis (batch=1 cells).
    """
    if ctx.mesh is None:
        return None, None, None
    ba = ctx.batch_axes
    mdl = ctx.model_axis
    if mode == "head":
        spec = P(None, ba, None, mdl, None)
    elif mode == "seq":
        spec = P(None, ba, mdl, None, None)
    elif mode == "seq_all":
        spec = P(None, None, tuple(list(ba) + [mdl]), None, None)
    else:
        raise ValueError(mode)
    len_spec = P(ba) if mode != "seq_all" else P(None)
    return spec, spec, len_spec


def serve_prefill(params, cfg: LMConfig, tokens, ctx: ShardCtx):
    """Prefill: (B, S) -> (last-token logits (B, V), caches, lengths)."""
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens, cfg, ctx)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    sc = cache_len_for(cfg, s)

    def body(carry, lp):
        x, aux = carry
        x, a, (k, v) = layer_forward(x, lp, cfg, ctx, positions)
        if sc < s:
            # sliding window: keep the trailing window, laid out in *ring*
            # order (slot = position % sc) so decode_step's ring writes
            # land consistently after wraparound
            k, v = k[:, s - sc:], v[:, s - sc:]
            off = (s - sc) % sc
            if off:
                k = jnp.roll(k, off, axis=1)
                v = jnp.roll(v, off, axis=1)
        return (x, aux + a), (k, v)

    body = _remat_wrap(body, cfg.remat if cfg.remat != "2level" else "full")
    (x, _), (ck, cv) = jax.lax.scan(
        body, (x, jnp.float32(0.0)),
        compute_cast(params["layers"], cfg.compute_dtype))
    x = apply_norm(cfg.norm, x, params["final_norm"])
    logits = lm_head_logits(params, cfg, x[:, -1:], ctx)[:, 0]
    lengths = jnp.full((b,), sc, jnp.int32)
    return logits, (ck, cv), lengths


def _write_cache_local(ck, cv, k_new, v_new, write_pos):
    """Per-batch dynamic row write. ck (B, S, Hkv, Dh), write_pos (B,)."""
    def upd(c, n, p):
        return jax.lax.dynamic_update_slice(c, n, (p, 0, 0))
    ck = jax.vmap(upd)(ck, k_new, write_pos)
    cv = jax.vmap(upd)(cv, v_new, write_pos)
    return ck, cv


def decode_step(params, cfg: LMConfig, tokens, positions, caches,
                ctx: ShardCtx, kv_mode: str = "head"):
    """One decode step.

    tokens (B, 1) int32; positions (B,) absolute positions of the new token;
    caches = (ck, cv, lengths) with ck/cv (L, B, Sc, Hkv, Dh).
    Returns (logits (B, V), new caches).
    """
    ck_all, cv_all, lengths = caches
    b = tokens.shape[0]
    sc = ck_all.shape[2]
    x = embed_lookup(params["embed"], tokens, cfg, ctx)
    pos2d = positions[:, None]
    write_pos = (positions % sc).astype(jnp.int32)  # ring buffer under SWA
    new_len = jnp.minimum(positions + 1, sc).astype(jnp.int32)

    layers_c = compute_cast(params["layers"], cfg.compute_dtype)

    def body(carry, li):
        # caches ride in the scan *carry* with per-layer dynamic-slice
        # updates: XLA keeps the multi-GiB cache stacks in place instead of
        # double-buffering them through scan xs->ys
        x, ck_all, cv_all = carry
        lp = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
            layers_c)
        ck = jax.lax.dynamic_index_in_dim(ck_all, li, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cv_all, li, 0, keepdims=False)
        q, k, v = _attn_qkv(x, lp, cfg, ctx, pos2d)
        q1 = q[:, 0]                                  # (B, H, Dh)
        if ctx.mesh is None or kv_mode == "local":
            ck, cv = _write_cache_local(ck, cv, k, v, write_pos)
            att = decode_attention_local(q1, ck, cv, new_len, backend="ref")
        elif kv_mode == "head":
            ck, cv = _write_cache_local(ck, cv, k, v, write_pos)
            att = decode_attention_local(q1, ck, cv, new_len, backend="auto")
        else:
            seq_axes = (tuple(list(ctx.batch_axes) + [ctx.model_axis])
                        if kv_mode == "seq_all" else (ctx.model_axis,))
            ck, cv, att = _decode_seqsharded(
                q1, k, v, ck, cv, write_pos, new_len, ctx, kv_mode, seq_axes)
        ck_all = jax.lax.dynamic_update_index_in_dim(ck_all, ck, li, 0)
        cv_all = jax.lax.dynamic_update_index_in_dim(cv_all, cv, li, 0)
        att = att.astype(x.dtype).reshape(b, 1, cfg.q_dim)
        x = x + ctx.constrain(att @ lp["wo"].astype(att.dtype),
                              ctx.batch_spec(None, None))
        ffn_out, _ = _ffn(x, lp, cfg, ctx)
        return (x + ffn_out, ck_all, cv_all), None

    (x, ck_new, cv_new), _ = jax.lax.scan(
        body, (x, ck_all, cv_all),
        jnp.arange(cfg.n_layers, dtype=jnp.int32))
    x = apply_norm(cfg.norm, x, params["final_norm"])
    logits = lm_head_logits(params, cfg, x, ctx)[:, 0]
    return logits, (ck_new, cv_new, new_len)


def _decode_seqsharded(q1, k_new, v_new, ck, cv, write_pos, new_len, ctx,
                       kv_mode, seq_axes):
    """Sequence-sharded cache write + distributed flash-decode merge."""
    from jax.experimental.shard_map import shard_map
    mesh = ctx.mesh
    ba = ctx.batch_axes
    cache_spec = (P(ba, ctx.model_axis, None, None) if kv_mode == "seq"
                  else P(None, seq_axes, None, None))
    b_spec = P(ba) if kv_mode == "seq" else P(None)
    q_spec = (P(ba, None, None) if kv_mode == "seq" else P(None, None, None))
    kv_new_spec = (P(ba, None, None, None) if kv_mode == "seq"
                   else P(None, None, None, None))

    def body(q_l, kn, vn, ck_l, cv_l, wp, nl):
        s_l = ck_l.shape[1]
        idx = jnp.int32(0)
        for ax in seq_axes:
            idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        start = idx * s_l
        loc = jnp.clip(wp - start, 0, s_l - 1)
        mine = (wp >= start) & (wp < start + s_l)

        def upd(c, n, p, m):
            cur = jax.lax.dynamic_slice(c, (p, 0, 0), (1,) + c.shape[1:])
            row = jnp.where(m, n, cur)
            return jax.lax.dynamic_update_slice(c, row, (p, 0, 0))

        ck_l = jax.vmap(upd)(ck_l, kn, loc, mine)
        cv_l = jax.vmap(upd)(cv_l, vn, loc, mine)
        local_len = shard_lengths(nl, idx, s_l)
        att = decode_attention_seqsharded(q_l, ck_l, cv_l, local_len,
                                          seq_axes)
        return ck_l, cv_l, att

    return shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, kv_new_spec, kv_new_spec, cache_spec, cache_spec,
                  b_spec, b_spec),
        out_specs=(cache_spec, cache_spec, q_spec),
        check_rep=False,
    )(q1, k_new, v_new, ck, cv, write_pos, new_len)
