"""openPangu-Ultra-MoE: a decoder-only language model on latent attention
and routed experts, as one chip's share of an expert-parallel deployment.

Residual blocks without biases and with SANDWICH norms (an RMSNorm before
and after each sub-layer, four gains a block):

    h <- h + RMSNorm(Attn(RMSNorm(h; g1)); g2)
    h <- h + RMSNorm(FFN(RMSNorm(h; g3)); g4)

The residual stream and the logits are float32, the matrix products
bfloat16 with float32 accumulation, attention scores, their softmax and
the router float32.

Latent attention keeps ONE row of ``kv_lora_rank + qk_rope_head_dim``
numbers per position and layer, whatever the head count: the normed
latent ``c`` that every head's keys and values are expanded from, and one
rotary key ``k_r`` that all heads share.  Two paths compute the one
function (tests/test_pangu_moe.py holds them equal):

  * ``expanded`` (prefill, the whole-sequence pass): keys and values are
    expanded per head from the latents of the positions attended, and
    attention is the ordinary kind with key width ``nope + rope`` and
    value width ``v``;
  * ``absorbed`` (the decode step): ``W_uk`` goes into the query and
    ``W_uv`` into the output, and the row attends over the latents as
    they lie in the cache; expanding them would cost ``heads * (nope + v)
    * kv_lora_rank`` multiply-adds a cached position at every step.
    Scores, mask, softmax and weights x latents are one Pallas kernel
    (``ops/flash_attention.py latent_decode_attention``; interpreted off
    the chip) that reads each row's latents once, to the row's depth.

The routed-expert layer is told which experts it holds
(``experts_held`` from ``expert_offset``).  It scores all ``n_experts``
with a sigmoid (or as the configuration's ``scoring_func`` says: Keye's
softmax), takes the ``experts_per_token`` largest over ALL of them,
normalises their weights to ``routed_scaling_factor``, and computes the
shared expert plus the part of the sum that its own experts give; what
the absent experts would add is the other chips' to compute.  No token is
dropped and no capacity is set: the assignments to held experts are
sorted by expert and go through a grouped product (``grouped_product``:
the Pallas ``megablox`` kernel on the chip), so the cost follows the
assignments.

Entry points: ``prefill_window`` (one window of a prompt against a row's
cache), ``decode_step`` (one token per live row, each at its own
position), ``__call__`` (a whole sequence, no cache: what the tests
compare with the plain reference) and, with ``n_mtp``, the multi-token
prediction module on top of it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpu_pipelines.models.decode_contract import (
    CacheKind, DecodeContract, window_positions)
from tpu_pipelines.models.evabyte import (
    NEG_INF, GatedMlp, rope, yarn_inv_freq, yarn_mscale)
from tpu_pipelines.ops.flash_attention import (
    LANES, grouped_attention, latent_block, latent_decode_attention)


@dataclasses.dataclass(frozen=True)
class PanguConfig:
    """The widths and counts of one model, as every module reads them.
    The defaults are openPangu-Ultra-MoE-718B as published, with every
    expert held."""

    vocab_size: int = 153600
    d_model: int = 7680
    n_layers: int = 61
    n_dense_layers: int = 3
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 18432
    d_expert: int = 2048
    n_experts: int = 256
    experts_held: int = 256
    expert_offset: int = 0
    experts_per_token: int = 8
    n_shared_experts: int = 1
    shared_average: bool = False
    routed_scaling_factor: float = 2.5
    rope_theta: float = 25600000.0
    rms_norm_eps: float = 1e-5
    n_mtp: int = 1
    # Scaled rotary positions (an ``evabyte.Yarn``); None: ``rope_theta``'s
    # own frequencies and the plain softmax scale.
    rope_scaling: Any = None
    # The router chooses by ``sigmoid(score) + e_score_correction_bias``
    # (a stored leaf) and weighs by the sigmoid alone.
    selection_bias: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @property
    def row_width(self) -> int:
        """Numbers a cached position holds in one layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim


# Rows a tile of the grouped product holds.  An expert here sees a few
# rows a step (4 of 128 at 8 choices in 256 experts), and a tile is the
# least it costs: as low as the bfloat16 layout allows with room to spare.
ROW_TILE = 32
# Weight tiles (rows of the contraction, columns) of the two products into
# the expert width and of the one out of it, (tk, tn): 3 MB each, so that a
# tile's read, not the grid's turn-over, is what a step of the kernel costs.
TILE_IN, TILE_OUT = (768, 2048), (2048, 768)
# The tile of a product by its weights' shape ``(k, n)``, where a sweep on
# the chip found one (openPangu's 7,680 x 2,048 experts: the two above;
# Command A+'s 4,096 x 4,096: PERF.md section 6, PR 35; Xing4.0's 3,584 x
# 1,024 at 2 and 4 rows an expert over 64: the whole matrix into the
# expert width, 578 and 661 us against 598 and 699 at (512, 1024), and
# half of it out, 578 and 667 us, where the whole read 577 us and once
# 1,711; PERF.md section 6, PR 42); ``TILE_IN`` where none was swept.
TILES = {
    (7680, 2048): TILE_IN, (2048, 7680): TILE_OUT,
    (4096, 4096): (4096, 512),
    (3584, 1024): (3584, 1024), (1024, 3584): (1024, 1792),
}


def grouped_product(rows, weights, sizes, tile):
    """``rows [m, k]`` sorted by group, ``weights [groups, k, n]``,
    ``sizes [groups]`` -> ``[m, n]`` float32: rows ``[sum(sizes[:g]),
    sum(sizes[:g + 1]))`` times ``weights[g]``; a row behind the last
    group holds nothing that may be used.  ``m`` is a multiple of
    ``ROW_TILE``.  On the chip the Pallas ``megablox`` kernel, which
    visits only the row tiles that a group touches; elsewhere XLA's own
    grouped product (tests/test_pangu_moe.py holds the two equal)."""
    if jax.default_backend() != "tpu":
        return jax.lax.ragged_dot(
            rows, weights, sizes, preferred_element_type=jnp.float32)
    return megablox(rows, weights, sizes, tile)


def megablox(rows, weights, sizes, tile, interpret: bool = False):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    k, n = weights.shape[1:]
    return gmm(
        rows, weights, sizes, preferred_element_type=jnp.float32,
        tiling=(ROW_TILE, min(tile[0], k), min(tile[1], n)),
        interpret=interpret)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * g`` in float32."""

    eps: float
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                       self.param_dtype)
        with jax.named_scope("norm"):
            x = x.astype(jnp.float32)
            ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            return x * jax.lax.rsqrt(ms + self.eps) * g.astype(jnp.float32)


# The most a prefill window's float32 scores may take when they are written
# out (heads x window x span x 4 bytes; openPangu's 128 x 256 x 1,024 are
# 134 MB).  Over it the window attends in blocks (``LatentAttention.
# blocked``): Xing4.0's 32 x 1,024 x 16,384 would be 2.1 GB beside 13.8 GB
# resident.
WINDOW_SCORE_BYTES = 256 * 2 ** 20
# Query heads that share one fetch of a key block in ``blocked``: what the
# kernel holds of them at 512 queries x 640 columns is 38 MB of VMEM.
BLOCKED_HEADS = 8


def softmax_scale(c) -> float:
    """What attention scores are multiplied with: the key width's inverse
    root, times YaRN's ``mscale(factor, mscale_all_dim) ** 2`` where the
    positions are scaled."""
    scale = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
    y = c.rope_scaling
    if y is None:
        return scale
    return scale * yarn_mscale(y.factor, y.mscale_all_dim) ** 2


class LatentAttention(nn.Module):
    cfg: PanguConfig

    def setup(self):
        c = self.cfg
        dense = lambda n, name: nn.Dense(
            n, use_bias=False, dtype=c.dtype, param_dtype=c.param_dtype,
            name=name)
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.param_dtype, name=name)
        h, r = c.n_heads, c.kv_lora_rank
        init = nn.initializers.lecun_normal()
        self.q_down = dense(c.q_lora_rank, "q_down")
        self.q_norm = norm("q_norm")
        self.q_up = self.param(
            "q_up", init,
            (c.q_lora_rank, h, c.qk_nope_head_dim + c.qk_rope_head_dim),
            c.param_dtype)
        self.kv_down = dense(c.row_width, "kv_down")
        self.kv_norm = norm("kv_norm")
        # W_ukv by its two halves: a head's keys and its values.
        self.k_up = self.param(
            "k_up", init, (r, h, c.qk_nope_head_dim), c.param_dtype)
        self.v_up = self.param(
            "v_up", init, (r, h, c.v_head_dim), c.param_dtype)
        self.o_proj = dense(c.d_model, "o_proj")
        self.scale = softmax_scale(c)

    def rotate(self, x, pos):
        """The rotary code of the configuration on x [b, l, h, rope]."""
        c = self.cfg
        y = c.rope_scaling
        if y is None:
            return rope(x, pos, c.rope_theta)
        turned = rope(x, pos, c.rope_theta, yarn_inv_freq(
            c.qk_rope_head_dim, c.rope_theta, y))
        gain = yarn_mscale(y.factor, y.mscale) \
            / yarn_mscale(y.factor, y.mscale_all_dim)
        return turned if gain == 1.0 else turned * gain

    def project(self, x, pos):
        """x [b, l, d_model], pos [b, l] -> the queries' two parts
        ``q_nope [b, l, h, nope]`` and ``q_rope [b, l, h, rope]`` (rotated)
        and the cache rows ``[b, l, kv_lora_rank + rope]``: the normed
        latent, then the one rotary key."""
        c = self.cfg
        with jax.named_scope("attention_proj"):
            x = x.astype(c.dtype)
            c_q = self.q_norm(self.q_down(x)).astype(c.dtype)
            q = jnp.einsum("blr,rhd->blhd", c_q, self.q_up.astype(c.dtype))
            q_rope = self.rotate(q[..., c.qk_nope_head_dim:], pos)
            down = self.kv_down(x)
            latent = self.kv_norm(down[..., :c.kv_lora_rank])
            k_r = self.rotate(down[..., None, c.kv_lora_rank:], pos)[:, :, 0]
            rows = jnp.concatenate([latent, k_r], -1).astype(c.dtype)
            return (q[..., :c.qk_nope_head_dim], q_rope.astype(c.dtype),
                    rows)

    def expanded(self, q_nope, q_rope, rows, ok):
        """Ordinary attention over keys and values expanded from
        ``rows [b, lk, r + rope]``; ``ok [b, lq, lk]``.
        -> [b, lq, h * v]."""
        with jax.named_scope("attention_core"), \
                jax.named_scope("mla.attend"):
            dtype, r = self.cfg.dtype, self.cfg.kv_lora_rank
            f32 = dict(preferred_element_type=jnp.float32)
            latent, k_r = rows[..., :r], rows[..., r:]
            k_nope = jnp.einsum(
                "bkr,rhd->bkhd", latent, self.k_up.astype(dtype))
            v = jnp.einsum("bkr,rhd->bkhd", latent, self.v_up.astype(dtype))
            score = (
                jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope, **f32)
                + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_r, **f32)
            ) * self.scale
            p = jax.nn.softmax(jnp.where(ok[:, None], score, NEG_INF), -1)
            out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(dtype), v)
            return out.reshape(out.shape[:2] + (-1,))

    def absorbed(self, q_nope, q_rope, cache, pos, klen: int):
        """One query a row over the latents themselves, as they lie:
        q_nope [b, h, nope], q_rope [b, h, rope]; row ``i`` attends over
        ``cache[i, :pos[i] + 1]`` of ``cache [slots, positions, r +
        rope]``, ``pos`` [b] under ``klen``.  -> [b, h * v]."""
        with jax.named_scope("attention_core"), \
                jax.named_scope("mla.attend"):
            dtype, r = self.cfg.dtype, self.cfg.kv_lora_rank
            q_lat = jnp.einsum(
                "bhd,rhd->bhr", q_nope, self.k_up.astype(dtype))
            # One kernel for scores, mask, softmax and weights x latents:
            # a cached row is its own key (every column) and value (the
            # first ``r``), read once and only to the row's depth.
            o_lat = latent_decode_attention(
                jnp.concatenate([q_lat, q_rope], -1), cache, pos, klen,
                scale=self.scale, r=r)
            out = jnp.einsum("bhr,rhd->bhd", o_lat, self.v_up.astype(dtype))
            return out.reshape(out.shape[0], -1)

    def blocked(self, q_nope, q_rope, rows, start):
        """One row's window over the row's own latents in the absorbed
        form, as ONE kernel (ops/flash_attention.py ``grouped_attention``):
        every head's key and value is the cached row itself, so the heads
        are the grouped queries of one key/value head, a key block is
        fetched once for ``BLOCKED_HEADS`` of them, the scores never leave
        the chip's fast memory and a block past the window's last position
        is neither fetched nor computed.  q_nope [1, W, h, nope], q_rope
        [1, W, h, rope] at positions ``start + [0, W)``; ``rows [1, span,
        r + rope]`` by position.  -> [1, W, h * v]."""
        with jax.named_scope("attention_core"), \
                jax.named_scope("mla.attend"):
            c, dtype, r = self.cfg, self.cfg.dtype, self.cfg.kv_lora_rank
            h, w = c.n_heads, q_nope.shape[1]
            g = BLOCKED_HEADS if h % BLOCKED_HEADS == 0 else h
            q_lat = jnp.einsum(
                "lhd,rhd->hlr", q_nope[0], self.k_up.astype(dtype),
                preferred_element_type=jnp.float32)
            q = jnp.concatenate(
                [q_lat, jnp.swapaxes(q_rope[0], 0, 1)], -1) * self.scale
            # columns to whole lanes: zeros add nothing to a score, and
            # the columns past ``r`` of the result are not read
            pad = -c.row_width % LANES
            q = jnp.pad(q.astype(dtype), ((0, 0), (0, 0), (0, pad)))
            keys = jnp.broadcast_to(
                jnp.pad(rows[0], ((0, 0), (0, pad)))[None],
                (h // g, rows.shape[1], c.row_width + pad))
            o_lat = grouped_attention(
                q.reshape(h // g, g, w, -1), keys, keys,
                jnp.arange(rows.shape[1]), start,
                lambda t, u: (u <= t) & (u >= 0))
            out = jnp.einsum(
                "hlr,rhd->lhd", o_lat.reshape(h, w, -1)[..., :r],
                self.v_up.astype(dtype))
            return out.reshape(1, w, -1)

    def full(self, x, pos):
        """A whole sequence under a causal mask, no cache."""
        q_nope, q_rope, rows = self.project(x, pos)
        with jax.named_scope("attention_core"):
            ok = pos[:, :, None] >= pos[:, None, :]
            out = self.expanded(q_nope, q_rope, rows, ok)
        with jax.named_scope("attention_proj"):
            return self.o_proj(out)

    def window(self, x, index, cache, span: int):
        """One window of one row.  x [1, W, d_model]; ``cache
        [1, positions, r + rope]`` gets the window's rows at
        ``[index * W, index * W + W)``, and the window attends over the
        first ``span`` positions (every prompt's windows lie inside), up
        to each query's own.  What lies past the prompt's end is masked
        or rewritten by the decode steps that follow."""
        w = x.shape[1]
        with jax.named_scope("attention_proj"):
            pos = index * w + jnp.arange(w)[None]
        q_nope, q_rope, rows = self.project(x, pos)
        with jax.named_scope("cache_write"):
            cache = jax.lax.dynamic_update_slice_in_dim(
                cache, rows, index * w, axis=1)
        if self.cfg.n_heads * w * span * 4 > WINDOW_SCORE_BYTES:
            out = self.blocked(q_nope, q_rope, cache[:, :span], index * w)
        else:
            with jax.named_scope("attention_core"):
                ok = pos[:, :, None] >= jnp.arange(span)[None, None, :]
                out = self.expanded(q_nope, q_rope, cache[:, :span], ok)
        with jax.named_scope("attention_proj"):
            return self.o_proj(out), cache

    def step(self, x, pos, cache, klen: int):
        """One token per row.  x [b, d_model], pos [b]; ``cache
        [slots, positions, r + rope]`` with ``slots >= b``: rows
        ``[0, b)`` are written at their own positions where they lie and
        attend over their own first ``pos + 1`` positions, all of them
        among the first ``klen``."""
        b = x.shape[0]
        with jax.named_scope("attention_proj"):
            one = x[:, None], pos[:, None]
        q_nope, q_rope, rows = self.project(*one)
        with jax.named_scope("cache_write"):
            for r in range(b):
                cache = jax.lax.dynamic_update_slice(
                    cache, rows[r][None], (r, pos[r], 0))
        out = self.absorbed(q_nope[:, 0], q_rope[:, 0], cache, pos, klen)
        with jax.named_scope("attention_proj"):
            return self.o_proj(out), cache


# How a router's float32 products over ALL experts become the scores whose
# largest are taken and weighed, by ``scoring_func``.
SCORES = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}


class RoutedExperts(nn.Module):
    """The shared experts and this chip's share of the routed ones.
    x [n, d_model] -> (y [n, d_model], picked [n, experts_held]): the
    layer's partial output, and which held experts each token chose.

    Shared by models/command_a.py.  What it reads of ``cfg``, all of it
    data: ``d_model``, ``d_expert``, ``n_experts`` (the router's outputs),
    ``experts_held`` from ``expert_offset``, ``experts_per_token``,
    ``routed_scaling_factor`` (1 where the source has none),
    ``n_shared_experts`` as ONE gated MLP of their joint width, whose
    output is their sum, or with ``shared_average`` their mean (0: no
    such leaf, and nothing added); ``scoring_func`` where it has the
    field (``SCORES``: a sigmoid where it has not); ``selection_bias``
    where it has the field; ``dtype`` and ``param_dtype``."""

    cfg: Any

    def setup(self):
        c = self.cfg
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=0)
        e, d, f = c.experts_held, c.d_model, c.d_expert
        self.router = self.param(
            "router", nn.initializers.lecun_normal(), (d, c.n_experts),
            c.param_dtype)
        self.shared = GatedMlp(
            d, c.n_shared_experts * f, c.dtype, c.param_dtype, name="shared"
        ) if c.n_shared_experts else None
        self.experts_gate = self.param(
            "experts_gate", init, (e, d, f), c.param_dtype)
        self.experts_up = self.param(
            "experts_up", init, (e, d, f), c.param_dtype)
        self.experts_down = self.param(
            "experts_down", init, (e, f, d), c.param_dtype)
        self.bias = self.param(
            "e_score_correction_bias", nn.initializers.zeros,
            (c.n_experts,), jnp.float32,
        ) if getattr(c, "selection_bias", False) else None

    def route(self, x):
        """-> weights [n, k] and expert ids [n, k] over ALL experts.  A
        selection bias chooses and does not weigh."""
        score = SCORES[getattr(self.cfg, "scoring_func", "sigmoid")]
        with jax.named_scope("mlp"), jax.named_scope("moe.route"):
            sigma = score(jnp.dot(
                x.astype(jnp.float32), self.router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            if self.bias is None:
                top, ids = jax.lax.top_k(sigma, self.cfg.experts_per_token)
            else:
                _, ids = jax.lax.top_k(
                    sigma + self.bias, self.cfg.experts_per_token)
                top = jnp.take_along_axis(sigma, ids, -1)
            weights = self.cfg.routed_scaling_factor * top / jnp.sum(
                top, -1, keepdims=True)
            return weights, ids

    def __call__(self, x):
        c = self.cfg
        k, e = c.experts_per_token, c.experts_held
        weights, ids = self.route(x)
        with jax.named_scope("mlp"):
            local = ids - c.expert_offset
            held = (local >= 0) & (local < e)
            x = x.astype(c.dtype)
            picked = jnp.sum(
                local[:, :, None] == jnp.arange(e)[None, None, :], 1,
                dtype=jnp.int32)
        with jax.named_scope("mlp"), jax.named_scope("moe.experts"):
            # Every (token, choice) pair is a row; the pairs of held
            # experts sorted by expert, the others behind them in a group
            # that no product visits.
            group = jnp.where(held, local, e).reshape(-1)
            group = jnp.pad(
                group, (0, -group.size % ROW_TILE), constant_values=e)
            order = jnp.argsort(group)
            sizes = jnp.sum(picked, 0)
            xs = x[jnp.minimum(order // k, x.shape[0] - 1)]
            grouped = lambda rows, w: grouped_product(
                rows, w.astype(c.dtype), sizes,
                TILES.get(w.shape[1:], TILE_IN))
            hidden = jax.nn.silu(grouped(xs, self.experts_gate)) \
                * grouped(xs, self.experts_up)
            ys = grouped(hidden.astype(c.dtype), self.experts_down)
            # Back in the order of the pairs, each times its weight; a row
            # outside every group holds whatever the product left there.
            ys = ys[jnp.argsort(order)[:held.size]].reshape(
                x.shape[0], k, -1)
            routed = jnp.sum(
                jnp.where(held[..., None], ys * weights[..., None], 0.0), 1)
        if self.shared is None:
            return routed, picked
        with jax.named_scope("mlp"), jax.named_scope("moe.shared"):
            shared = self.shared(x).astype(jnp.float32)
            if c.shared_average:
                shared = shared / c.n_shared_experts
            return shared + routed, picked


class PanguBlock(nn.Module):
    cfg: PanguConfig
    routed: bool

    def setup(self):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.param_dtype, name=name)
        self.attn_norm = norm("attn_norm")
        self.attn_post_norm = norm("attn_post_norm")
        self.ffn_norm = norm("ffn_norm")
        self.ffn_post_norm = norm("ffn_post_norm")
        self.attn = LatentAttention(c, name="attn")
        self.ffn = RoutedExperts(c, name="ffn") if self.routed else GatedMlp(
            c.d_model, c.d_ff, c.dtype, c.param_dtype, name="ffn")

    def _rest(self, h, a):
        """-> the stream after both sub-layers, and which held experts
        each token chose (none of them in a dense block)."""
        # The add that takes a sub-layer into the stream is booked with
        # the part that closes the sub-layer.
        a = self.attn_post_norm(a)
        with jax.named_scope("attention_proj"):
            h = h + a
        x = self.ffn_norm(h)
        if self.routed:
            with jax.named_scope("mlp"):
                rows = x.reshape(-1, x.shape[-1])
            y, picked = self.ffn(rows)
            with jax.named_scope("mlp"):
                y = y.reshape(x.shape)
                picked = picked.reshape(x.shape[:-1] + (-1,))
        else:
            y = self.ffn(x)
            with jax.named_scope("mlp"):
                picked = jnp.zeros(x.shape[:-1] + (0,), jnp.int32)
        y = self.ffn_post_norm(y)
        with jax.named_scope("mlp"):
            return h + y, picked

    def full(self, h, pos):
        return self._rest(h, self.attn.full(self.attn_norm(h), pos))[0]

    def window(self, h, index, cache, span: int):
        a, cache = self.attn.window(self.attn_norm(h), index, cache, span)
        return self._rest(h, a)[0], cache

    def step(self, h, pos, cache, klen: int):
        a, cache = self.attn.step(self.attn_norm(h), pos, cache, klen)
        h, picked = self._rest(h, a)
        return h, cache, picked


class PanguMoE(nn.Module):
    """batch {inputs [b, l]} -> logits [b, l, vocab]; with ``n_mtp`` also
    the prediction module's [b, l - 1, vocab], whose row ``t`` predicts
    token ``t + 2`` from the stream at ``t`` and token ``t + 1``."""

    cfg: PanguConfig
    # the block every layer and the prediction module are made of
    # (models/xing.py puts its own here)
    block_cls = PanguBlock

    def setup(self):
        c = self.cfg
        self.embed = nn.Embed(
            c.vocab_size, c.d_model, param_dtype=c.param_dtype, name="embed")
        self.blocks = [
            self.block_cls(
                c, routed=i >= c.n_dense_layers, name=f"layer_{i}")
            for i in range(c.n_layers)
        ]
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.param_dtype, name=name)
        self.final_norm = norm("final_norm")
        self.head = self.param(
            "head", nn.initializers.lecun_normal(),
            (c.d_model, c.vocab_size), c.param_dtype)
        if c.n_mtp:
            self.mtp_h_norm = norm("mtp_h_norm")
            self.mtp_e_norm = norm("mtp_e_norm")
            self.mtp_proj = nn.Dense(
                c.d_model, use_bias=False, dtype=c.dtype,
                param_dtype=c.param_dtype, name="mtp_proj")
            self.mtp_block = self.block_cls(
                c, routed=True, name="mtp_block")

    def blank_cache(self, batch: int, positions: int):
        """Per layer one array of latent rows, ``[batch, positions,
        kv_lora_rank + qk_rope_head_dim]``."""
        c = self.cfg
        return {
            f"layer_{i}": {"latent": jnp.zeros(
                (batch, positions, c.row_width), c.dtype)}
            for i in range(c.n_layers)
        }

    def head_logits(self, h):
        """Float32 logits: the product in the compute dtype, accumulated
        and handed out in float32."""
        with jax.named_scope("embed_head"):
            return jnp.dot(
                self.final_norm(h).astype(self.cfg.dtype),
                self.head.astype(self.cfg.dtype),
                preferred_element_type=jnp.float32)

    def prefill_window(self, tokens, n_valid, index, cache, span: int):
        """One window of a prompt: ``n_valid`` of the ``W`` tokens count.
        -> the row's cache and the logits [1, vocab] at the last valid
        position (the prompt's first new token when this is its last
        window)."""
        with jax.named_scope("embed_head"):
            h = self.embed(tokens).astype(jnp.float32)
        new = {}
        for i, block in enumerate(self.blocks):
            h, rows = block.window(
                h, index, cache[f"layer_{i}"]["latent"], span)
            new[f"layer_{i}"] = {"latent": rows}
        with jax.named_scope("embed_head"):
            last = jax.lax.dynamic_slice_in_dim(h, n_valid - 1, 1, axis=1)
            return new, self.head_logits(last[:, 0])

    def decode_step(self, tok, pos, cache, klen: int):
        """tok, pos [b] -> cache, logits [b, vocab], and which held
        experts each row chose, [b, expert layers * experts_held], layer
        by layer."""
        with jax.named_scope("embed_head"):
            h = self.embed(tok).astype(jnp.float32)
        new, picked = {}, []
        for i, block in enumerate(self.blocks):
            h, rows, chose = block.step(
                h, pos, cache[f"layer_{i}"]["latent"], klen)
            new[f"layer_{i}"] = {"latent": rows}
            picked.append(chose)
        logits = self.head_logits(h)
        with jax.named_scope("mlp"):
            return new, logits, jnp.concatenate(picked, -1)

    def __call__(self, batch: Dict[str, Any], *, deterministic: bool = True):
        inputs = jnp.asarray(batch["inputs"], jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(inputs.shape[1]), inputs.shape)
        with jax.named_scope("embed_head"):
            h = self.embed(inputs).astype(jnp.float32)
        for block in self.blocks:
            h = block.full(h, pos)
        logits = self.head_logits(h)
        if not self.cfg.n_mtp:
            return logits
        # h'_t = W_p [norm(h_t) ; norm(Emb(x_{t+1}))], one expert block,
        # then the model's own final norm and head.
        both = jnp.concatenate([
            self.mtp_h_norm(h[:, :-1]),
            self.mtp_e_norm(self.embed(inputs[:, 1:]).astype(jnp.float32)),
        ], -1)
        h2 = self.mtp_block.full(
            self.mtp_proj(both.astype(self.cfg.dtype)).astype(jnp.float32),
            pos[:, :-1])
        return logits, self.head_logits(h2)


def config_from(cls, hparams: Dict):
    """``cls`` (a dataclass of widths and counts with ``dtype`` and
    ``param_dtype``) from the keys of ``hparams`` that are its fields, each
    as its field's type, and from ``compute_dtype`` / ``param_dtype``;
    other keys are passed over."""
    hp = dict(hparams or {})
    kinds = {"float": float, "bool": bool, "str": str}
    fields = {f.name: kinds.get(f.type, int) for f in dataclasses.fields(cls)}
    return cls(
        **{k: fields[k](v) for k, v in hp.items()
           if k in fields and k not in ("dtype", "param_dtype")},
        dtype=jnp.dtype(hp.get("compute_dtype", "bfloat16")),
        param_dtype=jnp.dtype(hp.get("param_dtype", "bfloat16")),
    )


def tally_account(tally, held: int) -> Dict[str, Any]:
    """What a step's tally says of the experts held: ``tally`` is the
    live rows' assignments to each held expert, expert layer by expert
    layer, ``held`` experts a layer."""
    layers = [tally[i:i + held] for i in range(0, len(tally), held)]
    busy = [t for t in layers if sum(t)]
    return {
        "expert_assignments": int(sum(tally)),
        "experts_touched": sum(n > 0 for n in tally),
        # the fullest held expert over the mean, expert layers averaged
        "expert_load_ratio": (
            sum(max(t) * held / sum(t) for t in busy) / len(busy)
            if busy else None),
    }


def build_pangu_moe_model(hparams: Dict, mesh=None) -> PanguMoE:
    """``hparams``: fields of ``PanguConfig`` (the published model where
    left out), ``compute_dtype`` and ``param_dtype``; other keys (the
    names a driver reads, such as ``head_dim``) are passed over."""
    cfg = config_from(PanguConfig, hparams)
    if not 0 <= cfg.expert_offset <= cfg.n_experts - cfg.experts_held:
        raise ValueError(
            "the experts held must lie inside the router's outputs")
    return PanguMoE(cfg)


def make_continuous_decode_fns(
    model: PanguMoE,
    *,
    max_decode_len: int = 32,
    eos_id: int = 1,
    pad_id: int = 0,
    max_input_len: int = 64,
    prefill_window_len: int = 256,
):
    """openPangu's ``DecodeContract`` (models/decode_contract.py), of the
    decoder-only family (``DecodeContract.decoder_only``).  Its own:

      - one kind of cache, ``latent``: per layer ``[slots, positions,
        kv_lora_rank + qk_rope_head_dim]``, indexed by position from the
        prompt's first token on (``cache_positions``), written by every
        step, worked on in place: ``step`` writes row ``i`` at ``pos[i]``
        and attends over its first ``pos[i] + 1`` positions, fetched by
        whole key blocks (``klen`` bounds their number);
      - ``step_tally_len``: the held experts of every expert layer;
      - ``step_account``: the valid bytes, the key blocks fetched and
        the tally of the experts held.
    """
    c = model.cfg
    w = int(prefill_window_len)
    span, positions = window_positions(max_input_len, max_decode_len, w)
    row_bytes = c.n_layers * c.row_width * jnp.dtype(c.dtype).itemsize
    block = latent_block(positions)
    expert_layers, held = c.n_layers - c.n_dense_layers, c.experts_held

    def prefill_window(params, cache, tokens, n_valid, index):
        return model.apply(
            {"params": params}, tokens, n_valid, index, cache, span,
            method="prefill_window")

    def step(params, cache, tok, pos, encoded, enc_mask, klen: int):
        return model.apply(
            {"params": params}, tok, pos, cache, klen,
            method="decode_step")

    def blank_cache(batch: int):
        return model.blank_cache(batch, positions)

    def step_account(at, tally, bucket=None):
        """``at``: the live rows' positions; ``tally``: assignments to
        each held expert, layer by layer (``bucket``, the step's rows
        and positions, is not read).  The span is what the attention
        kernel fetches for the live rows: whole key blocks up to the one
        that holds a row's position."""
        return {
            "cache_bytes": {"latent": sum(t + 1 for t in at) * row_bytes},
            "cache_span_bytes": {"latent": sum(
                min((t // block + 1) * block, positions) for t in at
            ) * row_bytes},
            **tally_account(tally, held)}

    return DecodeContract.decoder_only(
        step=step,
        step_tally_len=expert_layers * held,
        prefill_window=prefill_window,
        prefill_window_len=w,
        blank_cache=blank_cache,
        cache_positions=positions,
        cache_kinds={
            "latent": CacheKind(True, written=True, in_place=True)},
        cache_kind_of=lambda path: "latent",
        step_account=step_account,
        max_decode_len=max_decode_len,
        eos_id=eos_id,
        pad_id=pad_id,
        max_input_len=max_input_len,
    )
