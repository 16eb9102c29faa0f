"""Top-level model: init / forward / loss / serve for all assigned archs.

Layer stacks are organized as *units*: the repeating pattern of the
architecture (1 layer for dense archs, the local/global pair for gemma2,
the 8-layer Mamba/attention block for jamba, the mLSTM/sLSTM pattern for
xlstm).  Unit parameters are stacked on a leading "layers" axis and the
stack runs under ``lax.scan`` (``cfg.scan_layers=False`` switches to a
Python loop — used by the roofline probe lowerings so every unit's FLOPs
appear in the HLO, and by the serve prefill which collects per-layer KV).

Entry points:
  init_params(key, cfg)             -> (param values, logical-axes tree)
  build_forward(cfg)                -> hidden-state forward fn
  loss_fn(cfg)                      -> (loss, metrics) fn  (chunked xent)
  make_serve_fns(cfg)               -> ServeFns(prefill, decode, prefill_chunk)
  init_caches / cache_layout        -> decode caches (+ dry-run specs)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks, layers
from repro.sharding.specs import Annotated, annotate, shard, split_params


# -- unit layout -----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class UnitLayout:
    prefix: Tuple[int, ...]          # absolute indices of unscanned layers
    unit_len: int
    n_units: int
    enc_units: int = 0               # whisper encoder stack (unit_len 1)

    @property
    def prefix_len(self) -> int:
        return len(self.prefix)


def unit_layout(cfg: ModelConfig) -> UnitLayout:
    enc = cfg.encoder_layers if cfg.is_encoder_decoder else 0
    if cfg.moe is not None and cfg.moe.first_dense_layers:
        pre = cfg.moe.first_dense_layers
        return UnitLayout(tuple(range(pre)), 1, cfg.num_layers - pre, enc)
    if cfg.family == "hybrid":
        ul = len(cfg.hybrid_pattern)
    elif cfg.family == "ssm":
        ul = len(cfg.xlstm.pattern)
    elif cfg.layer_pattern:
        ul = len(cfg.layer_pattern)
    else:
        ul = 1
    if cfg.num_layers % ul:
        raise ValueError(f"{cfg.name}: {cfg.num_layers} layers not divisible "
                         f"by unit pattern length {ul}")
    return UnitLayout((), ul, cfg.num_layers // ul, enc)


def _stack_units(unit_trees: List[Any]):
    """Stack a list of Annotated param trees on a leading 'layers' axis."""
    is_leaf = lambda x: isinstance(x, Annotated)

    def stack(*leaves):
        return Annotated(jnp.stack([l.value for l in leaves]),
                         ("layers", *leaves[0].axes))

    return jax.tree.map(stack, *unit_trees, is_leaf=is_leaf)


# -- init ------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig):
    """Returns (values_tree, logical_axes_tree)."""
    lay = unit_layout(cfg)
    keys = jax.random.split(key, 8 + lay.n_units + lay.enc_units)
    p: Dict[str, Any] = {
        "embed": layers.init_embedding(keys[0], cfg),
        "final_norm": layers.init_norm(keys[1], cfg),
    }
    if lay.prefix:
        kp = jax.random.split(keys[2], len(lay.prefix))
        p["prefix"] = {f"l{i}": blocks.init_block(kp[j], cfg, i)
                       for j, i in enumerate(lay.prefix)}
    units = []
    for u in range(lay.n_units):
        ku = jax.random.split(keys[3 + u], lay.unit_len)
        units.append({f"r{r}": blocks.init_block(
            ku[r], cfg, lay.prefix_len + u * lay.unit_len + r)
            for r in range(lay.unit_len)})
    p["units"] = _stack_units(units) if lay.n_units > 1 else units[0]

    if cfg.is_encoder_decoder:
        enc = []
        for u in range(lay.enc_units):
            enc.append({"r0": blocks.init_block(
                keys[3 + lay.n_units + u], cfg, u, encoder=True)})
        p["enc_units"] = _stack_units(enc) if lay.enc_units > 1 else enc[0]
        p["enc_final_norm"] = layers.init_norm(keys[2], cfg)

    if cfg.mtp:
        km = jax.random.split(keys[-1], 3)
        p["mtp"] = {
            "proj": annotate(layers.dense_init(
                km[0], (2 * cfg.d_model, cfg.d_model)), None, "d_model"),
            "block": blocks.init_block(km[1], cfg, 0),
            "norm": layers.init_norm(km[2], cfg),
        }
    values, axes = split_params(p)
    if cfg.param_dtype != "float32":
        values = jax.tree.map(lambda v: v.astype(cfg.param_dtype), values)
    return values, axes


# -- positions / input embedding ----------------------------------------------------

def _positions(cfg: ModelConfig, batch: Dict[str, jnp.ndarray]):
    tokens = batch["tokens"]
    b, s = tokens.shape
    if cfg.m_rope:
        patch = batch["patch_embeds"].shape[1] if "patch_embeds" in batch \
            else 0
        return layers.mrope_positions(b, s, patch)
    return layers.default_positions(b, s)


def _input_embed(cfg: ModelConfig, params, batch):
    x = layers.embed_tokens(cfg, params["embed"], batch["tokens"])
    if "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([pe, x[:, pe.shape[1]:]], axis=1)
        x = shard(x, "batch", "seq", "d_model")
    if cfg.pos_embed == "sinusoidal":
        # whisper decoder: absolute sinusoidal positions
        s, d = x.shape[1], x.shape[2]
        x = x + layers.sinusoidal_embedding(s, d, x.dtype)[None]
    return x


# -- encoder (whisper) ----------------------------------------------------------------

def _run_encoder(cfg: ModelConfig, params, frame_embeds):
    b, s, d = frame_embeds.shape
    x = frame_embeds.astype(cfg.dtype) \
        + layers.sinusoidal_embedding(s, d, cfg.dtype)[None]
    x = shard(x, "batch", "seq", "d_model")
    pos = layers.default_positions(b, s)
    lay = unit_layout(cfg)

    def unit(x, up):
        x, _, _ = blocks.block_forward(cfg, up["r0"], x, pos, 0,
                                       encoder=True)
        return x

    x = _run_units(cfg, params["enc_units"], lay.enc_units, unit, x)
    return layers.apply_norm(cfg, params["enc_final_norm"], x)


def _run_units(cfg: ModelConfig, unit_params, n_units: int, unit_fn, x,
               aux0=None):
    """Scan or loop ``unit_fn`` over stacked unit params.

    unit_fn(x, unit_param_tree) -> x  (or (x, aux) when aux0 is given).
    """
    with_aux = aux0 is not None
    if n_units == 1:
        out = unit_fn(x, unit_params)
        return out if not with_aux else (out[0], aux0 + out[1])

    fn = unit_fn
    if cfg.remat != "none":
        policy = (jax.checkpoint_policies.checkpoint_dots
                  if cfg.remat == "dots" else None)
        fn = jax.checkpoint(unit_fn, policy=policy)

    if cfg.scan_layers:
        def body(carry, up):
            if with_aux:
                xx, aux = carry
                xx, a = fn(xx, up)
                return (xx, aux + a), None
            return fn(carry, up), None

        carry0 = (x, aux0) if with_aux else x
        carry, _ = jax.lax.scan(body, carry0, unit_params)
        return carry

    aux = aux0
    for u in range(n_units):
        up = jax.tree.map(lambda a: a[u], unit_params)
        if with_aux:
            x, a = fn(x, up)
            aux = aux + a
        else:
            x = fn(x, up)
    return (x, aux) if with_aux else x


# -- forward -------------------------------------------------------------------------

def build_forward(cfg: ModelConfig):
    """Returns forward(params, batch) -> (hidden (B,S,d), aux_loss)."""
    lay = unit_layout(cfg)

    def forward(params, batch):
        x = _input_embed(cfg, params, batch)
        pos = _positions(cfg, batch)
        aux = jnp.zeros((), jnp.float32)
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = _run_encoder(cfg, params, batch["frame_embeds"])

        for i in lay.prefix:
            x, a, _ = blocks.block_forward(cfg, params["prefix"][f"l{i}"],
                                           x, pos, i, enc_out=enc_out)
            aux = aux + a

        def unit(xx, up):
            a_sum = jnp.zeros((), jnp.float32)
            for r in range(lay.unit_len):
                xx, a, _ = blocks.block_forward(
                    cfg, up[f"r{r}"], xx, pos, lay.prefix_len + r,
                    enc_out=enc_out)
                a_sum = a_sum + a
            return xx, a_sum

        x, aux = _run_units(cfg, params["units"], lay.n_units, unit, x,
                            aux0=aux)
        x = layers.apply_norm(cfg, params["final_norm"], x)
        return x, aux

    return forward


# -- loss ---------------------------------------------------------------------------

def _xent_chunk(cfg: ModelConfig, embed_params, h, targets):
    """Mean-sum NLL over one chunk. h: (B,C,d), targets: (B,C) (-1 pad)."""
    logits = layers.logits_from_hidden(cfg, embed_params, h)   # fp32
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.maximum(targets, 0)
    picked = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    valid = (targets >= 0).astype(jnp.float32)
    nll = (lse - picked) * valid
    return nll.sum(), valid.sum()


def _chunked_xent(cfg: ModelConfig, embed_params, hidden, targets):
    s = hidden.shape[1]
    ck = cfg.loss_chunk or s
    nb = math.ceil(s / ck)
    fn = _xent_chunk if nb == 1 or cfg.remat == "none" \
        else jax.checkpoint(_xent_chunk, static_argnums=(0,))
    total = jnp.zeros((), jnp.float32)
    count = jnp.zeros((), jnp.float32)
    for i in range(nb):
        t, c = fn(cfg, embed_params, hidden[:, i * ck:(i + 1) * ck],
                  targets[:, i * ck:(i + 1) * ck])
        total = total + t
        count = count + c
    return total / jnp.maximum(count, 1.0)


def loss_fn(cfg: ModelConfig):
    """Returns loss(params, batch) -> (scalar, metrics dict)."""
    forward = build_forward(cfg)

    def loss(params, batch):
        hidden, aux = forward(params, batch)
        targets = batch["targets"]
        nll = _chunked_xent(cfg, params["embed"], hidden, targets)
        metrics = {"nll": nll, "aux_loss": aux}
        total = nll
        if cfg.moe is not None:
            total = total + cfg.moe.router_aux_loss * aux
        if cfg.mtp:
            mtp_nll = _mtp_loss(cfg, params, hidden, batch)
            metrics["mtp_nll"] = mtp_nll
            total = total + cfg.mtp_loss_weight * mtp_nll
        metrics["loss"] = total
        return total, metrics

    return loss


def _mtp_loss(cfg: ModelConfig, params, hidden, batch):
    """DeepSeek multi-token prediction: predict t+2 from [h_t; emb(t+1)]."""
    mp = params["mtp"]
    tokens, targets = batch["tokens"], batch["targets"]
    b, s = tokens.shape
    dt = hidden.dtype
    h = layers.apply_norm(cfg, mp["norm"], hidden[:, :-1])
    nxt = layers.embed_tokens(cfg, params["embed"], tokens[:, 1:])
    z = jnp.concatenate([h, nxt.astype(dt)], axis=-1) @ mp["proj"].astype(dt)
    pos = layers.default_positions(b, s - 1)
    z, _, _ = blocks.block_forward(cfg, mp["block"], z, pos, 0)
    # target for position t is token t+2 == targets shifted left by one
    return _chunked_xent(cfg, params["embed"], z, targets[:, 1:])


# -- serve: caches ------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch_size: int, max_len: int,
                dtype=jnp.bfloat16):
    """Decode caches: {"prefix": {l<i>: cache}, "units": stacked cache}."""
    lay = unit_layout(cfg)
    caches: Dict[str, Any] = {}
    if lay.prefix:
        caches["prefix"] = {
            f"l{i}": blocks.init_block_cache(cfg, i, batch_size, max_len,
                                             dtype)
            for i in lay.prefix}
    unit_caches = []
    for u in range(lay.n_units):
        unit_caches.append({
            f"r{r}": blocks.init_block_cache(
                cfg, lay.prefix_len + r, batch_size, max_len, dtype)
            for r in range(lay.unit_len)})
    if lay.n_units > 1:
        caches["units"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                       *unit_caches)
    else:
        caches["units"] = unit_caches[0]
    return caches


def cache_logical_axes(cfg: ModelConfig):
    """Logical-axes tree matching init_caches output."""
    lay = unit_layout(cfg)
    axes: Dict[str, Any] = {}
    if lay.prefix:
        axes["prefix"] = {f"l{i}": blocks.cache_axes(cfg, i)
                          for i in lay.prefix}
    unit_axes = {f"r{r}": blocks.cache_axes(cfg, lay.prefix_len + r)
                 for r in range(lay.unit_len)}
    if lay.n_units > 1:
        unit_axes = jax.tree.map(
            lambda ax: ("layers", *ax), unit_axes,
            is_leaf=lambda x: isinstance(x, tuple))
    axes["units"] = unit_axes
    return axes


# -- serve: paged caches ----------------------------------------------------------------

def supports_paged(cfg: ModelConfig) -> bool:
    """Whether this arch can serve from paged KV pools.

    Paging covers position-indexed attention caches only: every layer
    must be kind "A" (self-attention or MLA).  State archs (mamba /
    xlstm / hybrid) carry O(1) recurrent state — there is nothing to
    page — and encoder-decoder archs need a one-shot whole-encoder
    cross cache plus blocking prefill.  Such archs keep serving from
    the contiguous layout.
    """
    if cfg.is_encoder_decoder or cfg.pos_embed == "sinusoidal":
        return False
    return all(blocks.layer_kind(cfg, i) == "A"
               for i in range(cfg.num_layers))


def init_paged_caches(cfg: ModelConfig, num_pages: int, page_size: int,
                      dtype=jnp.bfloat16):
    """Paged pools, same tree shape as :func:`init_caches` — leaves are
    (P, page_size, ...) physical pools instead of (B, C, ...) per-slot
    caches (stacked units gain the leading "layers" axis as usual).
    Every leaf shares one page-id space; page 0 is scratch."""
    if not supports_paged(cfg):
        raise ValueError(f"{cfg.name}: arch does not support paged KV "
                         "(needs all-attention layers, no encoder)")
    lay = unit_layout(cfg)
    caches: Dict[str, Any] = {}
    if lay.prefix:
        caches["prefix"] = {
            f"l{i}": blocks.init_paged_block_cache(cfg, i, num_pages,
                                                   page_size, dtype)
            for i in lay.prefix}
    unit_caches = []
    for u in range(lay.n_units):
        unit_caches.append({
            f"r{r}": blocks.init_paged_block_cache(
                cfg, lay.prefix_len + r, num_pages, page_size, dtype)
            for r in range(lay.unit_len)})
    if lay.n_units > 1:
        caches["units"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                       *unit_caches)
    else:
        caches["units"] = unit_caches[0]
    return caches


def paged_cache_logical_axes(cfg: ModelConfig):
    """Logical-axes tree matching init_paged_caches output."""
    lay = unit_layout(cfg)
    axes: Dict[str, Any] = {}
    if lay.prefix:
        axes["prefix"] = {f"l{i}": blocks.paged_cache_axes(cfg, i)
                          for i in lay.prefix}
    unit_axes = {f"r{r}": blocks.paged_cache_axes(cfg, lay.prefix_len + r)
                 for r in range(lay.unit_len)}
    if lay.n_units > 1:
        unit_axes = jax.tree.map(
            lambda ax: ("layers", *ax), unit_axes,
            is_leaf=lambda x: isinstance(x, tuple))
    axes["units"] = unit_axes
    return axes


# -- serve: prefill / decode -----------------------------------------------------------

class ServeFns(NamedTuple):
    """The three pjit-able serve steps (see :func:`make_serve_fns`)."""

    prefill: Any
    decode: Any
    prefill_chunk: Any


def make_serve_fns(cfg: ModelConfig, cache_dtype=jnp.bfloat16):
    """Returns ``ServeFns(prefill, decode, prefill_chunk)``.

    ``cache_dtype`` sets the KV/latent cache storage dtype the prefill
    builds (decode and prefill_chunk consume whatever they are given).
    bf16 is the serving default; fp32 buys exact-parity debugging at 2x
    cache bytes.

    prefill(params, batch, max_len) -> (last_logits (B,V), caches)
    decode(params, caches, tokens (B,1), cur_len) -> (logits, caches)
    prefill_chunk(params, caches, tokens (B,T), offset, last_idx)
        -> (logits (B,V) at ``last_idx``, caches)

    ``cur_len`` is a scalar (synchronized decode: every row at the same
    position) or a (B,) int32 vector of per-slot position counters
    (continuous batching: each row advances independently and its KV
    lands at its own cache offset via the cache_update scatter).

    ``prefill_chunk`` resumes prefill from a *partial* cache: the chunk
    tokens sit at absolute positions ``offset + i`` (``offset`` scalar
    or (B,) vector), attend the already-written cache prefix plus their
    own causal keys through ``kernels/prefill_attention``, and scatter
    their KV (or advance the mamba/xlstm scan carry) in place — so
    prefill compiles **once**, at one chunk shape, for any prompt
    length.  ``last_idx`` (traced scalar) marks the last *real* token
    of a right-padded final chunk: logits come from that position, pad
    KV is kept off ring caches, and pad tokens leave state caches
    untouched.  Not available for encoder-decoder archs (the cross-
    attention KV needs one whole-encoder pass — serve admission falls
    back to blocking prefill there).

    ``cfg.decode_attn_impl`` selects the decode attention path for every
    attention/MLA layer in the stack: "flash" = the length-aware
    ``kernels/decode_attention`` sweep that skips cache blocks beyond
    each row's ``cur_len``; "dense" = masked full-cache attend; "auto"
    = flash on TPU (see blocks.decode_attn_impl).
    """
    lay = unit_layout(cfg)

    def prefill(params, batch, max_len: int):
        x = _input_embed(cfg, params, batch)
        pos = _positions(cfg, batch)
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = _run_encoder(cfg, params, batch["frame_embeds"])

        caches: Dict[str, Any] = {}

        def run_layer(xx, bp, idx):
            xx, _, kv = blocks.block_forward(cfg, bp, xx, pos, idx,
                                             enc_out=enc_out, collect_kv=True)
            kind = blocks.layer_kind(cfg, idx)
            if kind in ("m", "s", "M"):
                return xx, kv          # kv already is the decode cache
            x_enc_kv = None
            if kind == "X":
                from repro.models import attention as attn_mod
                _, xk, xv = attn_mod.project_qkv(
                    cfg, bp["cross"], enc_out, None, kv_x=enc_out,
                    rope=False)
                x_enc_kv = (xk, xv)
            return xx, blocks.prefill_block_cache(cfg, idx, kv, max_len,
                                                  x_enc_kv=x_enc_kv,
                                                  dtype=cache_dtype)

        if lay.prefix:
            caches["prefix"] = {}
            for i in lay.prefix:
                x, c = run_layer(x, params["prefix"][f"l{i}"], i)
                caches["prefix"][f"l{i}"] = c

        unit_caches = []
        for u in range(lay.n_units):
            up = params["units"] if lay.n_units == 1 else \
                jax.tree.map(lambda a: a[u], params["units"])
            uc = {}
            for r in range(lay.unit_len):
                x, c = run_layer(x, up[f"r{r}"], lay.prefix_len + r)
                uc[f"r{r}"] = c
            unit_caches.append(uc)
        caches["units"] = unit_caches[0] if lay.n_units == 1 else \
            jax.tree.map(lambda *xs: jnp.stack(xs), *unit_caches)

        x = layers.apply_norm(cfg, params["final_norm"], x)
        logits = layers.logits_from_hidden(cfg, params["embed"], x[:, -1:])
        return logits[:, 0], caches

    def decode_step(params, caches, tokens, cur_len):
        x = layers.embed_tokens(cfg, params["embed"], tokens)
        if cfg.pos_embed == "sinusoidal":
            cur = jnp.asarray(cur_len, jnp.int32)
            row = layers.sinusoidal_row(cur, x.shape[-1], x.dtype)
            x = x + (row[:, None, :] if cur.ndim else row[None, None])
        if lay.prefix:
            for i in lay.prefix:
                x, c = blocks.block_decode(
                    cfg, params["prefix"][f"l{i}"], x,
                    caches["prefix"][f"l{i}"], cur_len, i)
                caches["prefix"][f"l{i}"] = c

        def unit(xx, up_uc):
            up, uc = up_uc
            new_uc = {}
            for r in range(lay.unit_len):
                xx, c = blocks.block_decode(cfg, up[f"r{r}"], xx,
                                            uc[f"r{r}"], cur_len,
                                            lay.prefix_len + r)
                new_uc[f"r{r}"] = c
            return xx, new_uc

        if lay.n_units == 1:
            x, caches["units"] = unit(x, (params["units"], caches["units"]))
        elif cfg.scan_layers:
            def body(xx, up_uc):
                return unit(xx, up_uc)

            x, caches["units"] = jax.lax.scan(
                body, x, (params["units"], caches["units"]))
        else:
            ucs = []
            for u in range(lay.n_units):
                sl = lambda a: a[u]
                x, uc = unit(x, (jax.tree.map(sl, params["units"]),
                                 jax.tree.map(sl, caches["units"])))
                ucs.append(uc)
            caches["units"] = jax.tree.map(lambda *xs: jnp.stack(xs), *ucs)

        x = layers.apply_norm(cfg, params["final_norm"], x)
        logits = layers.logits_from_hidden(cfg, params["embed"], x)
        return logits[:, 0], caches

    def prefill_chunk(params, caches, tokens, offset, last_idx):
        if cfg.is_encoder_decoder:
            raise NotImplementedError(
                "chunked prefill is not available for encoder-decoder "
                "archs; use the whole-prompt prefill")
        last_idx = jnp.asarray(last_idx, jnp.int32)
        valid_len = last_idx + 1
        x = layers.embed_tokens(cfg, params["embed"], tokens)

        def run(xx, bp, c, idx):
            return blocks.block_prefill_chunk(cfg, bp, xx, c, offset,
                                              valid_len, idx)

        if lay.prefix:
            for i in lay.prefix:
                x, c = run(x, params["prefix"][f"l{i}"],
                           caches["prefix"][f"l{i}"], i)
                caches["prefix"][f"l{i}"] = c

        def unit(xx, up_uc):
            up, uc = up_uc
            new_uc = {}
            for r in range(lay.unit_len):
                xx, c = run(xx, up[f"r{r}"], uc[f"r{r}"],
                            lay.prefix_len + r)
                new_uc[f"r{r}"] = c
            return xx, new_uc

        if lay.n_units == 1:
            x, caches["units"] = unit(x, (params["units"], caches["units"]))
        elif cfg.scan_layers:
            x, caches["units"] = jax.lax.scan(
                lambda xx, up_uc: unit(xx, up_uc), x,
                (params["units"], caches["units"]))
        else:
            ucs = []
            for u in range(lay.n_units):
                sl = lambda a: a[u]
                x, uc = unit(x, (jax.tree.map(sl, params["units"]),
                                 jax.tree.map(sl, caches["units"])))
                ucs.append(uc)
            caches["units"] = jax.tree.map(lambda *xs: jnp.stack(xs), *ucs)

        x = layers.apply_norm(cfg, params["final_norm"], x)
        x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
        logits = layers.logits_from_hidden(cfg, params["embed"], x_last)
        return logits[:, 0], caches

    return ServeFns(prefill, decode_step, prefill_chunk)


class PagedServeFns(NamedTuple):
    """The two pjit-able paged serve steps (see make_paged_serve_fns)."""

    decode: Any
    prefill_chunk: Any


def make_paged_serve_fns(cfg: ModelConfig):
    """Returns ``PagedServeFns(decode, prefill_chunk)``.

    decode(params, caches, tokens (B,1), cur_len (B,), page_table)
        -> (logits (B,V), caches)
    prefill_chunk(params, caches, tokens (B,T), offset (B,),
                  last_idx (B,), page_table) -> (logits (B,V), caches)

    ``caches`` are :func:`init_paged_caches` pools; ``page_table`` is
    (B, NB) int32 mapping each slot's logical blocks to physical pages
    (rows the scheduler masks to 0 touch only the scratch page).
    Paged serving is always continuous, so ``cur_len``/``offset`` are
    per-row vectors, and — unlike the contiguous ``prefill_chunk`` —
    ``last_idx`` is a (B,) vector too: the batched admission path runs
    several requests' chunks in ONE (B, T) dispatch, each row at its
    own offset with its own fill.  Rows with ``last_idx == -1`` are
    passengers (idle or decoding): their ``valid_len`` clamps to 0, so
    they write nothing, and their logits row is garbage the engine
    discards.
    """
    if not supports_paged(cfg):
        raise ValueError(f"{cfg.name}: arch does not support paged KV "
                         "(needs all-attention layers, no encoder)")
    lay = unit_layout(cfg)

    def _run_stack(params, caches, x, run):
        """Shared prefix + units sweep for both paged steps.

        Every pool stays whole across the sweep: the kernels address
        layer ``u`` of a stacked (L, P, page_size, ...) leaf through a
        layer index, so the scan carries the unit pools and no step
        slices, copies or restacks a pool.  An unstacked leaf (a prefix
        layer, or the single unit) runs as a stack of one, a free
        reshape."""
        stack1 = lambda tree: jax.tree.map(lambda a: a[None], tree)
        unstack1 = lambda tree: jax.tree.map(lambda a: a[0], tree)
        if lay.prefix:
            for i in lay.prefix:
                x, c = run(x, params["prefix"][f"l{i}"],
                           stack1(caches["prefix"][f"l{i}"]), 0, i)
                caches["prefix"][f"l{i}"] = unstack1(c)

        def unit(xx, up, uc, u):
            uc = dict(uc)
            for r in range(lay.unit_len):
                xx, uc[f"r{r}"] = run(xx, up[f"r{r}"], uc[f"r{r}"], u,
                                      lay.prefix_len + r)
            return xx, uc

        if lay.n_units == 1:
            x, uc = unit(x, params["units"], stack1(caches["units"]), 0)
            caches["units"] = unstack1(uc)
        elif cfg.scan_layers:
            def body(carry, up_u):
                (xx, uc), (up, u) = carry, up_u
                return unit(xx, up, uc, u), None

            (x, caches["units"]), _ = jax.lax.scan(
                body, (x, caches["units"]),
                (params["units"], jnp.arange(lay.n_units, dtype=jnp.int32)))
        else:
            for u in range(lay.n_units):
                x, caches["units"] = unit(
                    x, jax.tree.map(lambda a: a[u], params["units"]),
                    caches["units"], u)
        return layers.apply_norm(cfg, params["final_norm"], x), caches

    def decode(params, caches, tokens, cur_len, page_table):
        x = layers.embed_tokens(cfg, params["embed"], tokens)

        def run(xx, bp, c, layer, idx):
            return blocks.block_paged_decode(cfg, bp, xx, c, layer, cur_len,
                                             page_table, idx)

        x, caches = _run_stack(params, caches, x, run)
        logits = layers.logits_from_hidden(cfg, params["embed"], x)
        return logits[:, 0], caches

    def prefill_chunk(params, caches, tokens, offset, last_idx, page_table):
        last_idx = jnp.asarray(last_idx, jnp.int32)
        valid_len = jnp.maximum(last_idx + 1, 0)
        x = layers.embed_tokens(cfg, params["embed"], tokens)

        def run(xx, bp, c, layer, idx):
            return blocks.block_paged_prefill_chunk(
                cfg, bp, xx, c, layer, offset, valid_len, page_table, idx)

        x, caches = _run_stack(params, caches, x, run)
        # per-row last real token (vector last_idx — batched admissions)
        idx = jnp.clip(last_idx, 0, x.shape[1] - 1)
        x_last = jnp.take_along_axis(
            x, idx[:, None, None].astype(jnp.int32), axis=1)   # (B,1,d)
        logits = layers.logits_from_hidden(cfg, params["embed"], x_last)
        return logits[:, 0], caches

    return PagedServeFns(decode, prefill_chunk)
