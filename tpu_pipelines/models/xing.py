"""Xing4.0-29B-A4B: latent attention and routed experts (the classes of
models/pangu_moe.py) around a residual path of ``hc_mult`` streams that
learned, per-token matrices mix (manifold-constrained hyper-connections,
mHC).

A token's stream is ``X [hc_mult, d_model]`` float32: the embedding copied
into every row at the bottom, the rows summed before the final norm at the
top.  A sub-layer ``F`` (latent attention, then the dense MLP or the
expert layer) does not see ``h + F(h)``.  With its own ``phi [n C, 2n +
n^2]``, biases and three scalars it reads a mixture and writes back
through a doubly stochastic matrix (``StreamMix``):

    x^     = flatten(X) / sqrt(mean(flatten(X)^2) + hc_eps)     no gain
    H_pre  = sigmoid(a_pre x^ phi_pre + b_pre)                  [n]
    H_post = 2 sigmoid(a_post x^ phi_post + b_post)             [n]
    M      = exp(clip(a_res mat(x^ phi_res) + b_res, lo, hi))   [n, n]
    20 times: M <- M / (rows' sums + hc_eps), M <- M / (columns' sums +
    hc_eps);  H_res = M
    u      = sum_i H_pre[i] X[i]
    y      = F(RMSNorm(u; g))
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y

all of it float32; no norm behind a sub-layer.  ``F``'s products are
bfloat16 with float32 accumulation as in openPangu.

Latent attention is ``pangu_moe.LatentAttention`` under the
configuration's ``rope_scaling`` (YaRN: blended inverse frequencies, the
softmax scale times ``mscale ** 2``); the experts are
``pangu_moe.RoutedExperts`` with the selection bias: the top
``experts_per_token`` of ``sigmoid(score) + e_score_correction_bias``,
weighed by their sigmoids alone.  The cache, the entry points and the
decode contract are openPangu's: one ``latent`` kind of ``kv_lora_rank +
qk_rope_head_dim`` numbers a position a layer.

Device operations of the mixing carry the scopes ``mhc.mix`` (the
coefficients) and ``mhc.apply`` (``u`` and the write-back) inside the word
that closes the sub-layer (``attention_proj``, ``mlp``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpu_pipelines.models import pangu_moe
from tpu_pipelines.models.evabyte import GatedMlp, Yarn
from tpu_pipelines.models.pangu_moe import (
    LatentAttention, PanguConfig, PanguMoE, RMSNorm, RoutedExperts,
    config_from)


@dataclasses.dataclass(frozen=True)
class XingConfig(PanguConfig):
    """``PanguConfig``'s fields with Xing4.0-29B-A4B's published sizes as
    defaults, every expert held, and the residual path's own."""

    vocab_size: int = 131072
    d_model: int = 3584
    n_layers: int = 40
    n_dense_layers: int = 2
    n_heads: int = 32
    q_lora_rank: int = 768
    d_ff: int = 9216
    d_expert: int = 1024
    n_experts: int = 64
    experts_held: int = 64
    experts_per_token: int = 4
    routed_scaling_factor: float = 2.0
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    rope_scaling: Any = Yarn(
        factor=64.0, original_max_position_embeddings=4096, beta_fast=32.0,
        beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
    selection_bias: bool = True
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0


class StreamMix(nn.Module):
    """One sub-layer's mixing of the streams.  ``part``: the word the
    sub-layer closes with, which the mixing's operations are booked under.
    """

    cfg: XingConfig
    part: str

    def setup(self):
        c = self.cfg
        n = c.hc_mult
        self.phi = self.param(
            "phi", nn.initializers.lecun_normal(),
            (n * c.d_model, 2 * n + n * n), c.param_dtype)
        scalar = lambda name: self.param(
            name, nn.initializers.constant(0.01), (), jnp.float32)
        self.alpha = [scalar(f"{k}_alpha") for k in ("pre", "post", "res")]
        zeros = lambda name, shape: self.param(
            name, nn.initializers.zeros, shape, jnp.float32)
        self.b_pre, self.b_post = zeros("b_pre", (n,)), zeros("b_post", (n,))
        self.b_res = zeros("b_res", (n, n))

    def coefficients(self, x):
        """x [tokens, n, C] -> ``H_pre`` [n, tokens], ``H_post``
        [n, tokens], ``H_res`` [n, n, tokens]: the tokens last, so that
        the 40 normalisations run over whole vectors of them."""
        c = self.cfg
        n, eps = c.hc_mult, c.hc_eps
        with jax.named_scope(self.part), jax.named_scope("mhc.mix"):
            flat = x.reshape(x.shape[0], -1)
            flat = flat * jax.lax.rsqrt(
                jnp.mean(jnp.square(flat), -1, keepdims=True) + eps)
            z = jnp.einsum(
                "tk,kj->jt", flat, self.phi.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            a_pre, a_post, a_res = self.alpha
            pre = jax.nn.sigmoid(a_pre * z[:n] + self.b_pre[:, None])
            post = 2.0 * jax.nn.sigmoid(
                a_post * z[n:2 * n] + self.b_post[:, None])
            m = jnp.exp(jnp.clip(
                a_res * z[2 * n:].reshape(n, n, -1) + self.b_res[..., None],
                c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max))
            for _ in range(c.hc_sinkhorn_iters):
                m = m / (jnp.sum(m, 1, keepdims=True) + eps)
                m = m / (jnp.sum(m, 0, keepdims=True) + eps)
            return pre, post, m

    def read(self, x, pre):
        """-> ``u`` [tokens, C]."""
        with jax.named_scope(self.part), jax.named_scope("mhc.apply"):
            return sum(
                pre[i][:, None] * x[:, i] for i in range(self.cfg.hc_mult))

    def write(self, x, post, res, y):
        """-> ``X'`` [tokens, n, C] from the sub-layer's ``y``
        [tokens, C]."""
        n = self.cfg.hc_mult
        with jax.named_scope(self.part), jax.named_scope("mhc.apply"):
            y = y.astype(jnp.float32)
            return jnp.stack([
                sum(res[i, j][:, None] * x[:, j] for j in range(n))
                + post[i][:, None] * y for i in range(n)], 1)


class XingBlock(nn.Module):
    """Both sub-layers on streams ``X [..., n, C]``; the leading axes are
    the sub-layers' own (``[b, l]`` for a pass or a window, ``[b]`` for a
    step) and the mixing sees them as one axis of tokens."""

    cfg: XingConfig
    routed: bool

    def setup(self):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.param_dtype, name=name)
        self.attn_mix = StreamMix(c, "attention_proj", name="attn_mix")
        self.ffn_mix = StreamMix(c, "mlp", name="ffn_mix")
        self.attn_norm, self.ffn_norm = norm("attn_norm"), norm("ffn_norm")
        self.attn = LatentAttention(c, name="attn")
        self.ffn = RoutedExperts(c, name="ffn") if self.routed else GatedMlp(
            c.d_model, c.d_ff, c.dtype, c.param_dtype, name="ffn")

    def _around(self, mix, norm, x, sub_layer):
        """``sub_layer``: normed ``u [..., C]`` -> ``(y [..., C], *rest)``.
        -> ``(X', *rest)``."""
        lead = x.shape[:-2]
        tokens = x.reshape((-1,) + x.shape[-2:])
        pre, post, res = mix.coefficients(tokens)
        u = mix.read(tokens, pre).reshape(lead + x.shape[-1:])
        y, *rest = sub_layer(norm(u))
        out = mix.write(tokens, post, res, y.reshape(tokens.shape[0], -1))
        return (out.reshape(x.shape), *rest)

    def _ffn(self, x):
        """-> the streams after the FFN, and which held experts each
        token chose (none of them in a dense block)."""
        def sub_layer(u):
            if not self.routed:
                with jax.named_scope("mlp"):
                    none = jnp.zeros(u.shape[:-1] + (0,), jnp.int32)
                return self.ffn(u), none
            with jax.named_scope("mlp"):
                rows = u.reshape(-1, u.shape[-1])
            y, picked = self.ffn(rows)
            with jax.named_scope("mlp"):
                return (y.reshape(u.shape),
                        picked.reshape(u.shape[:-1] + (-1,)))

        return self._around(self.ffn_mix, self.ffn_norm, x, sub_layer)

    def full(self, x, pos):
        x, = self._around(
            self.attn_mix, self.attn_norm, x,
            lambda u: (self.attn.full(u, pos),))
        return self._ffn(x)[0]

    def window(self, x, index, cache, span: int):
        x, cache = self._around(
            self.attn_mix, self.attn_norm, x,
            lambda u: self.attn.window(u, index, cache, span))
        return self._ffn(x)[0], cache

    def step(self, x, pos, cache, klen: int):
        x, cache = self._around(
            self.attn_mix, self.attn_norm, x,
            lambda u: self.attn.step(u, pos, cache, klen))
        x, picked = self._ffn(x)
        return x, cache, picked


class XingMoE(PanguMoE):
    """``PanguMoE``'s entry points, cache and head over ``XingBlock``s."""

    cfg: XingConfig
    block_cls = XingBlock

    def streams(self, h):
        """h [..., C] -> ``X_0`` [..., n, C]: every stream a copy."""
        with jax.named_scope("embed_head"):
            h = h.astype(jnp.float32)[..., None, :]
            return jnp.broadcast_to(
                h, h.shape[:-2] + (self.cfg.hc_mult, h.shape[-1]))

    def merged(self, x):
        """X [..., n, C] -> the streams' sum [..., C]."""
        with jax.named_scope("embed_head"):
            return jnp.sum(x, -2)

    def prefill_window(self, tokens, n_valid, index, cache, span: int):
        x = self.streams(self.embed(tokens))
        new = {}
        for i, block in enumerate(self.blocks):
            x, rows = block.window(
                x, index, cache[f"layer_{i}"]["latent"], span)
            new[f"layer_{i}"] = {"latent": rows}
        with jax.named_scope("embed_head"):
            last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        return new, self.head_logits(self.merged(last[:, 0]))

    def decode_step(self, tok, pos, cache, klen: int):
        x = self.streams(self.embed(tok))
        new, picked = {}, []
        for i, block in enumerate(self.blocks):
            x, rows, chose = block.step(
                x, pos, cache[f"layer_{i}"]["latent"], klen)
            new[f"layer_{i}"] = {"latent": rows}
            picked.append(chose)
        logits = self.head_logits(self.merged(x))
        with jax.named_scope("mlp"):
            return new, logits, jnp.concatenate(picked, -1)

    def hidden(self, inputs):
        """inputs [b, l] -> the merged stream before the final norm,
        [b, l, C], and the positions."""
        inputs = jnp.asarray(inputs, jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(inputs.shape[1]), inputs.shape)
        x = self.streams(self.embed(inputs))
        for block in self.blocks:
            x = block.full(x, pos)
        return self.merged(x), pos

    def __call__(self, batch: Dict[str, Any], *, deterministic: bool = True):
        inputs = jnp.asarray(batch["inputs"], jnp.int32)
        h, pos = self.hidden(inputs)
        logits = self.head_logits(h)
        if not self.cfg.n_mtp:
            return logits
        # h'_t = W_m [norm(h_t) ; norm(Emb(x_{t+1}))] copied into the
        # streams, one expert block, merged, the model's own norm and head.
        both = jnp.concatenate([
            self.mtp_h_norm(h[:, :-1]),
            self.mtp_e_norm(self.embed(inputs[:, 1:]).astype(jnp.float32)),
        ], -1)
        x2 = self.mtp_block.full(
            self.streams(self.mtp_proj(both.astype(self.cfg.dtype))),
            pos[:, :-1])
        return logits, self.head_logits(self.merged(x2))


def build_xing_model(hparams: Dict, mesh=None) -> XingMoE:
    """``hparams``: fields of ``XingConfig`` (the published model where
    left out), ``rope_scaling`` as the source's group (None: plain
    frequencies), ``compute_dtype`` and ``param_dtype``; other keys are
    passed over."""
    hp = dict(hparams or {})
    scaling = hp.pop("rope_scaling", XingConfig.rope_scaling)
    cfg = dataclasses.replace(
        config_from(XingConfig, hp), rope_scaling=Yarn.of(scaling))
    if not 0 <= cfg.expert_offset <= cfg.n_experts - cfg.experts_held:
        raise ValueError(
            "the experts held must lie inside the router's outputs")
    return XingMoE(cfg)


# The decode contract is openPangu's, letter for letter: one ``latent``
# kind by position, ``step_account`` with the valid bytes, the key blocks
# fetched and the tally of the experts held.
make_continuous_decode_fns = pangu_moe.make_continuous_decode_fns
