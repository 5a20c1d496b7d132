"""openPangu-Ultra-MoE (models/pangu_moe.py) against its plain reference
(benchmark/reference/pangu_moe.py) on seeded weights, at a small size on
the CPU: the whole-sequence forward, prefill by window and then decode
through the latent cache, the two forms of latent attention, the expert
layer's shares against the uncut layer, the prediction module, and the
same through a real ``GenerativeEngine``.

Size: 3 layers (1 dense, 2 with experts), d_model 64, 4 heads, latents of
16 + 8, 32 experts of width 32 with 8 a token, 8 of them held (4 shares),
windows of 16, context 96 + 64; weights from benchmark/weights.py with the
spreads of the router and of the query / key expansions raised, so that
the eight chosen are not a matter of rounding and attention is peaked: a
fault in the cache or in the routed sum then moves the logits by far more
than a tolerance.

Tolerances.  The program in float32 and the reference compute the same
function in another order of summation (windows, the absorbed form, rows
sorted by expert against a masked loop), so their logits differ by float32
rounding: observed 2e-5 at a logit spread of 1; ``F32_TOL`` 2e-4 leaves a
decade for other seeds.  In bfloat16 (the served precision) over 48 decoded
positions the root mean square of the logits' error was 0.050 to 0.068 of
their spread on three seeds (a token whose ninth expert lies within a
bfloat16 step of its eighth changes experts); ``BF16_TOL`` 0.12 is under
twice that.  Leaving the routed part out moves the logits by 0.34 of their
spread and leaving the post-norms out by 0.57: the tests hold both over
twice ``BF16_TOL``.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.generative

VOCAB, WINDOW, HELD, EXPERTS, TOP_K = 96, 16, 8, 32, 8
HP = dict(
    vocab_size=VOCAB, d_model=64, n_layers=3, n_dense_layers=1, n_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, d_ff=96, d_expert=32,
    n_experts=EXPERTS, experts_held=HELD, expert_offset=0,
    experts_per_token=TOP_K, n_mtp=0,
)
RULES = {
    "embed/embedding": 0.3, "router": 0.5, "q_up": 0.5, "k_up": 0.5,
    "experts_gate": 0.125, "experts_up": 0.125, "experts_down": 0.177,
    "scale": "around_one", "kernel": "fan_in", "v_up": "fan_in",
    "head": "fan_in",
}
F32_TOL, BF16_TOL = 2e-4, 0.12
rms = lambda e: float(np.sqrt(np.mean(np.square(e))))
MAX_IN, MAX_OUT = 96, 64
ROW = 16 + 8                 # numbers a cached position holds in a layer


def build(dtype="float32", seed=7, **over):
    import jax

    from benchmark import weights
    from tpu_pipelines.models import pangu_moe

    model = pangu_moe.build_pangu_moe_model(
        {**HP, **over, "compute_dtype": dtype, "param_dtype": dtype})
    sample = {"inputs": np.ones((1, MAX_IN), np.int32),
              "targets": np.ones((1, 8), np.int32)}
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), sample)["params"])
    return model, weights.make_weights(shapes, RULES, seed)


@pytest.fixture(scope="module")
def f32():
    return build()


def reference_params(params, n_layers=HP["n_layers"]):
    from benchmark import weights
    from benchmark.reference import pangu_moe as ref

    return ref.from_served_tree(weights.flat_leaves(params), n_layers)


REFERENCE_PASS = []


def reference_logits(params, tokens):
    """[len, vocab] for one sequence.  The pass is causal, so the sequence
    is padded to a multiple of 32 and the pass compiled once a length."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import pangu_moe as ref

    if not REFERENCE_PASS:
        REFERENCE_PASS.append(jax.jit(
            lambda rp, tokens: ref.head_logits(rp, ref.hidden(rp, tokens))))
    n = len(tokens)
    padded = np.zeros((-(-n // 32) * 32,), np.int32)
    padded[:n] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(REFERENCE_PASS[0](
            reference_params(params), jnp.asarray(padded)))[:n]


def prompt(seed, n):
    return np.random.default_rng(seed).integers(
        2, VOCAB, size=n).astype(np.int32)


DECODE_FNS = {}


def decode_fns(model, **over):
    """The contract of ``model``, made once for each set of keywords, with
    its window and step programs (``fns.jitted``) compiled once too."""
    import jax

    from tpu_pipelines.models.pangu_moe import make_continuous_decode_fns

    key = (id(model), tuple(sorted(over.items())))
    if key not in DECODE_FNS:
        fns = make_continuous_decode_fns(model, **{**dict(
            max_decode_len=MAX_OUT, eos_id=VOCAB, max_input_len=MAX_IN,
            prefill_window_len=WINDOW), **over})
        fns.jitted = (
            jax.jit(fns.prefill_window),
            jax.jit(fns.step, static_argnums=6))
        DECODE_FNS[key] = (model, fns)      # the model kept: its id is the key
    return DECODE_FNS[key][1]


def prefill(fns, params, tokens):
    """A prompt's windows into a blank row -> (cache, last logits)."""
    window = fns.jitted[0]
    cache = fns.blank_cache(1)
    for m in range(-(-len(tokens) // WINDOW)):
        part = tokens[m * WINDOW:(m + 1) * WINDOW]
        padded = np.zeros((1, WINDOW), np.int32)
        padded[0, :len(part)] = part
        cache, logits = window(
            params, cache, padded, np.int32(len(part)), np.int32(m))
    return cache, logits


def through_the_cache(params, fns, tokens, n_new):
    """Greedy decode of one sequence through the contract's own programs:
    prefill by window, then single steps.  -> (tokens [n_new], logits
    [n_new, vocab])."""
    import jax.numpy as jnp

    step = fns.jitted[1]
    cache, logits = prefill(fns, params, tokens)
    out, rows = [], []
    pos = len(tokens)
    none = jnp.zeros((1, 0))
    while True:
        rows.append(np.asarray(logits[0]))
        out.append(int(np.argmax(rows[-1])))
        if len(out) == n_new:
            return np.asarray(out, np.int32), np.stack(rows)
        cache, logits, _ = step(
            params, cache, np.asarray(out[-1:], np.int32),
            np.asarray([pos], np.int32), none, none, fns.cache_positions)
        pos += 1


# (prompt length, new tokens): ends inside a window; ends on a window's
# last position; one token; the longest prompt and the longest answer.
CASES = [(37, 40), (48, 30), (1, 20), (96, 64)]


@pytest.mark.parametrize("n", [50, 16, 7, 160])
def test_forward_matches_the_reference(f32, n):
    model, params = f32
    tokens = prompt(n, n)
    got = np.asarray(model.apply({"params": params}, {"inputs": tokens[None]}))
    want = reference_logits(params, tokens)
    assert got.shape == (1, n, VOCAB)
    assert np.abs(got[0] - want).max() < F32_TOL
    assert want.std() > 0.5          # the logits are not all alike


@pytest.mark.parametrize("n_prompt,n_new", CASES)
def test_prefill_by_window_then_decode_matches_the_reference(
        f32, n_prompt, n_new):
    """Windows written into a by-position cache, then steps through the
    absorbed form over it: each step's logits against the reference's one
    full pass over the prompt with the served tokens behind it."""
    model, params = f32
    tokens = prompt(n_prompt, n_prompt)
    served, logits = through_the_cache(
        params, decode_fns(model), tokens, n_new)
    whole = np.concatenate([tokens, served])
    want = reference_logits(params, whole)[n_prompt - 1:-1]
    assert np.abs(logits - want).max() < F32_TOL
    assert len(set(served.tolist())) > 3     # not one token over and over


def test_absorbed_attention_is_the_expanded_attention(f32):
    """One function, two paths: the decode step's form over the latents
    themselves against keys and values expanded per head."""
    import jax.numpy as jnp

    from tpu_pipelines.models import pangu_moe

    model, params = f32
    attn = pangu_moe.LatentAttention(model.cfg)
    p = {"params": params["layer_1"]["attn"]}
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(5, 40, 64)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(40), (5, 40))
    q_n, q_r, rows = attn.apply(p, x, pos, method="project")
    depth = jnp.asarray([39, 0, 17, 5, 30])
    ok = jnp.arange(40)[None, :] <= depth[:, None]
    take = lambda q: q[jnp.arange(5), depth]
    one = attn.apply(p, take(q_n), take(q_r), rows, depth, 40,
                     method="absorbed")
    two = attn.apply(
        p, take(q_n)[:, None], take(q_r)[:, None], rows, ok[:, None],
        method="expanded")[:, 0]
    assert np.abs(np.asarray(one) - np.asarray(two)).max() < 1e-5
    assert np.asarray(two).std() > 0.1


def test_served_precision_stays_near_the_reference():
    """bfloat16 weights, products and cache, as served."""
    model, params = build("bfloat16")
    tokens = prompt(3, 37)
    served, logits = through_the_cache(
        params, decode_fns(model), tokens, 48)
    want = reference_logits(params, np.concatenate([tokens, served]))
    want = want[36:-1]
    assert rms(logits - want) < BF16_TOL * want.std()


# ------------------------------------------------------- the expert layer


def expert_layer(cfg_over, layer_params, x):
    from tpu_pipelines.models import pangu_moe

    cfg = pangu_moe.build_pangu_moe_model(
        {**HP, **cfg_over, "compute_dtype": "float32",
         "param_dtype": "float32"}).cfg
    return pangu_moe.RoutedExperts(cfg).apply({"params": layer_params}, x)


@pytest.fixture(scope="module")
def whole_layer():
    """One expert layer with all 32 experts held, and 24 tokens."""
    _, params = build(experts_held=EXPERTS)
    x = np.random.default_rng(11).normal(size=(24, 64)).astype(np.float32)
    return params["layer_1"]["ffn"], x


def reference_layer(layer, x, what="experts"):
    import jax

    from benchmark.reference import pangu_moe as ref

    flat = {"ffn/" + "/".join(k): v for k, v in _flat(layer).items()}
    with jax.default_matmul_precision("highest"):
        if what == "shared":
            return np.asarray(ref.gated(flat, "ffn/shared", x, "f32"))
        return np.asarray(ref.experts(
            flat, "ffn", x, "f32", ref.TOP_K, ref.SCALING, 0))


def _flat(tree, at=()):
    out = {}
    for k, v in tree.items():
        out.update(_flat(v, at + (k,)) if isinstance(v, dict)
                   else {at + (k,): v})
    return out


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(
        whole_layer):
    """Four chips of 8 experts each: what every share gives for its own
    experts, the shared expert counted once, is the uncut reference's
    layer; and every token's eight choices are computed by somebody."""
    layer, x = whole_layer
    shared = reference_layer(layer, x, "shared")
    total, chosen = shared.copy(), 0
    for share in range(EXPERTS // HELD):
        cut = slice(share * HELD, (share + 1) * HELD)
        part = {**layer, **{
            k: layer[k][cut]
            for k in ("experts_gate", "experts_up", "experts_down")}}
        y, picked = expert_layer(
            {"expert_offset": share * HELD}, part, x)
        assert picked.shape == (24, HELD)
        chosen += int(np.asarray(picked).sum())
        total += np.asarray(y) - shared
    want = reference_layer(layer, x)
    assert np.abs(total - want).max() < F32_TOL
    assert chosen == 24 * TOP_K
    # a share alone is not the layer
    assert rms(np.asarray(y) - want) > 0.1 * want.std()


def test_no_token_is_dropped_when_all_choose_the_same_experts(whole_layer):
    """Every token's eight are the eight held here: each expert sees all
    24 tokens, and all of them are computed (a capacity would drop most)."""
    layer, x = whole_layer
    router = -np.ones((64, EXPERTS), np.float32)
    router[:, :HELD] = 1.0
    x = np.abs(x)
    part = {**layer, "router": router, **{
        k: layer[k][:HELD]
        for k in ("experts_gate", "experts_up", "experts_down")}}
    y, picked = expert_layer({}, part, x)
    assert np.asarray(picked).tolist() == [[1] * HELD] * 24
    want = reference_layer({**layer, "router": router}, x)
    assert np.abs(np.asarray(y) - want).max() < F32_TOL
    shared = reference_layer(layer, x, "shared")
    assert rms(want - shared) > 0.5 * want.std()     # the routed part counts


def test_the_chips_grouped_product_is_xlas(whole_layer):
    """``grouped_product`` has two forms: the Pallas ``megablox`` kernel
    (what the chip runs; here through the interpreter) and XLA's
    ``ragged_dot`` (what this backend runs).  Rows sorted by group, a group
    left empty, rows behind the last group: equal wherever a group's rows
    lie, at the tiles the model hands the kernel."""
    import jax
    import jax.numpy as jnp

    from tpu_pipelines.models import pangu_moe

    layer, x = whole_layer
    rows = jnp.asarray(np.tile(x, (3, 1))[:64])            # [64, 64]
    sizes = jnp.asarray([3, 0, 10, 1, 7, 0, 5, 2], jnp.int32)
    weights = layer["experts_gate"][:HELD]
    want = jax.lax.ragged_dot(
        rows, weights, sizes, preferred_element_type=jnp.float32)
    got = pangu_moe.megablox(
        rows, weights, sizes, pangu_moe.TILE_IN, interpret=True)
    assert 64 % pangu_moe.ROW_TILE == 0
    filled = int(sizes.sum())
    assert np.abs(np.asarray(got - want)[:filled]).max() < 1e-5
    assert np.asarray(want)[:filled].std() > 0.1
    assert pangu_moe.grouped_product(
        rows, weights, sizes, pangu_moe.TILE_IN).shape == (64, 32)


# ------------------------- the router's scoring function, no shared expert


def keye_layer(seed=5, **over):
    """One expert layer as models/keye.py builds it (a softmax router over
    16 experts, 4 a token, NO shared expert), every expert held unless
    ``over`` says otherwise, with seeded weights."""
    import jax

    from benchmark import weights
    from tpu_pipelines.models import keye, pangu_moe

    cfg = keye.build_keye_model({
        "vocab_size": VOCAB, "d_model": 64, "n_layers": 1, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "mrope_section": [2, 3, 3],
        "index_heads": 2, "index_dim": 8, "index_topk": 8, "d_expert": 32,
        "n_experts": 16, "experts_held": 16, "experts_per_token": 4,
        **over, "compute_dtype": "float32", "param_dtype": "float32"}).cfg
    layer = pangu_moe.RoutedExperts(cfg)
    x = np.random.default_rng(seed).normal(size=(24, 64)).astype(np.float32)
    shapes = jax.eval_shape(
        lambda: layer.init(jax.random.key(0), jax.numpy.asarray(x))["params"])
    return layer, weights.make_weights(shapes, RULES, seed), x


def test_softmax_scoring_against_a_plain_restatement():
    """``scoring_func`` softmax: g = softmax(x Wr) over ALL 16, the 4
    largest, weights g_e / their sum (``routed_scaling_factor`` 1), the
    sum of the chosen experts' gated MLPs and nothing else."""
    layer, params, x = keye_layer()
    y, picked = layer.apply({"params": params}, x)
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    logits = x.astype(np.float64) @ p["router"]
    g = np.exp(logits - logits.max(-1, keepdims=True))
    g /= g.sum(-1, keepdims=True)
    want = np.zeros((24, 64))
    for t in range(24):
        top = np.argsort(-g[t], kind="stable")[:4]
        assert sorted(np.flatnonzero(np.asarray(picked)[t])) == sorted(top)
        for e in top:
            gate = x[t] @ p["experts_gate"][e]
            hidden = gate / (1 + np.exp(-gate)) * (x[t] @ p["experts_up"][e])
            want[t] += g[t, e] / g[t, top].sum() * (
                hidden @ p["experts_down"][e])
    assert np.abs(np.asarray(y) - want).max() < F32_TOL
    assert want.std() > 0.05
    # the sigmoid over the same weights chooses the same experts (both are
    # monotone in the router's product) and weighs them otherwise
    other, same = pangu_sigmoid(layer, params, x)
    assert np.array_equal(np.asarray(same), np.asarray(picked))
    assert rms(np.asarray(other) - want) > 0.02 * want.std()


def pangu_sigmoid(layer, params, x):
    import dataclasses

    from tpu_pipelines.models import pangu_moe

    cfg = dataclasses.replace(layer.cfg, scoring_func="sigmoid")
    return pangu_moe.RoutedExperts(cfg).apply({"params": params}, x)


def test_no_shared_expert_builds_no_shared_leaf_and_adds_nothing():
    layer, params, x = keye_layer()
    assert set(params) == {
        "router", "experts_gate", "experts_up", "experts_down"}
    # with the routed part silenced the layer gives exactly nothing
    y, _ = layer.apply(
        {"params": {**params, "experts_down": params["experts_down"] * 0}},
        x)
    assert np.abs(np.asarray(y)).max() == 0
    # openPangu's own layer keeps its shared expert
    _, pangu = build()
    assert "shared" in pangu["layer_1"]["ffn"]


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_shares_add_up_without_a_shared_expert(scoring):
    """Four chips of 4 experts each under either scoring function and no
    shared expert: the shares' parts add up to the layer with every expert
    held, every token's four choices are computed by somebody, and (the
    softmax) the sum is reference/keye.py's layer."""
    import jax

    from benchmark.reference import keye as ref

    layer, params, x = keye_layer(scoring_func=scoring)
    whole, _ = layer.apply({"params": params}, x)
    total, chosen = 0.0, 0
    for share in range(4):
        cut = slice(share * 4, share * 4 + 4)
        part = {**params, **{
            k: params[k][cut]
            for k in ("experts_gate", "experts_up", "experts_down")}}
        held, _, _ = keye_layer(
            scoring_func=scoring, experts_held=4, expert_offset=share * 4)
        y, picked = held.apply({"params": part}, x)
        assert picked.shape == (24, 4)
        chosen += int(np.asarray(picked).sum())
        total = total + np.asarray(y)
    assert np.abs(total - np.asarray(whole)).max() < F32_TOL
    assert chosen == 24 * 4
    assert rms(np.asarray(y) - np.asarray(whole)) > 0.1 * np.asarray(
        whole).std()
    if scoring == "softmax":
        flat = {"ffn/" + k: v for k, v in params.items()}
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.experts(flat, "ffn", x, "f32", 4))
        assert np.abs(total - want).max() < F32_TOL


@pytest.mark.parametrize("model", ["pangu_moe", "command_a", "xing"])
def test_the_sigmoid_layer_lowers_to_what_it_did_before_the_scoring_key(
        model):
    """``RoutedExperts`` as each of the three routed cells builds it (their
    tests' own small sizes) lowers to the same text as the layer did before
    ``scoring_func`` and ``n_shared_experts`` 0 were read (PR 44): the
    class below holds that PR's parent's three methods, letter for letter.
    (tests/test_device_parts.py holds the cells' whole step and window
    programs to their stored hashes besides.)"""
    import importlib

    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from tpu_pipelines.models import pangu_moe
    from tpu_pipelines.models.evabyte import GatedMlp
    from tpu_pipelines.models.pangu_moe import (
        ROW_TILE, TILE_IN, TILES, grouped_product)

    class Before(pangu_moe.RoutedExperts):
        def setup(self):
            c = self.cfg
            init = nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
                batch_axis=0)
            e, d, f = c.experts_held, c.d_model, c.d_expert
            self.router = self.param(
                "router", nn.initializers.lecun_normal(), (d, c.n_experts),
                c.param_dtype)
            self.shared = GatedMlp(
                d, c.n_shared_experts * f, c.dtype, c.param_dtype,
                name="shared")
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
            with jax.named_scope("mlp"), jax.named_scope("moe.route"):
                sigma = jax.nn.sigmoid(jnp.dot(
                    x.astype(jnp.float32), self.router.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST))
                if self.bias is None:
                    top, ids = jax.lax.top_k(
                        sigma, self.cfg.experts_per_token)
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
                ys = ys[jnp.argsort(order)[:held.size]].reshape(
                    x.shape[0], k, -1)
                routed = jnp.sum(
                    jnp.where(held[..., None], ys * weights[..., None], 0.0),
                    1)
            with jax.named_scope("mlp"), jax.named_scope("moe.shared"):
                shared = self.shared(x).astype(jnp.float32)
                if c.shared_average:
                    shared = shared / c.n_shared_experts
                return shared + routed, picked

    tiny = importlib.import_module("test_" + model)
    cfg = tiny.build()[0].cfg
    assert getattr(cfg, "scoring_func", "sigmoid") == "sigmoid"
    x = jnp.zeros((24, cfg.d_model), jnp.float32)
    texts = []
    for cls in (pangu_moe.RoutedExperts, Before):
        layer = cls(cfg)
        params = jax.eval_shape(
            lambda: layer.init(jax.random.key(0), x)["params"])
        texts.append(jax.jit(
            lambda p, x: layer.apply({"params": p}, x)).lower(
                params, x).as_text())
    assert texts[0] == texts[1] and len(texts[0]) > 10000


@pytest.mark.parametrize("left_out", ["routed", "post_norms"])
def test_the_tolerance_would_notice_a_term_left_out(f32, left_out):
    import jax.numpy as jnp
    from flax import linen as nn

    model, params = f32
    tokens = prompt(9, 50)
    want = reference_logits(params, tokens)
    if left_out == "routed":
        import jax

        broken = jax.tree_util.tree_map_with_path(
            lambda p, x: x * 0 if "experts_down" in str(p[-1]) else x,
            params)
        got = model.apply({"params": broken}, {"inputs": tokens[None]})
    else:
        def skip(next_fun, args, kwargs, context):
            if (context.module.name or "").endswith("post_norm"):
                return args[0].astype(jnp.float32)
            return next_fun(*args, **kwargs)

        with nn.intercept_methods(skip):
            got = model.apply({"params": params}, {"inputs": tokens[None]})
    good = model.apply({"params": params}, {"inputs": tokens[None]})
    assert np.abs(np.asarray(good[0]) - want).max() < F32_TOL
    assert rms(np.asarray(got[0]) - want) > 2 * BF16_TOL * want.std()


def test_prediction_module_matches_the_reference():
    """``n_mtp`` 1: the module's logits for token ``t + 2``, and the main
    logits unchanged by its presence."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import pangu_moe as ref

    model, params = build(n_mtp=1)
    tokens = prompt(21, 40)
    main, extra = model.apply({"params": params}, {"inputs": tokens[None]})
    rp = reference_params(params)
    assert "mtp_proj/kernel" in rp and "mtp_block/ffn/router" in rp
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.mtp_logits(rp, jnp.asarray(tokens)))
        want_main = np.asarray(
            ref.head_logits(rp, ref.hidden(rp, jnp.asarray(tokens))))
    assert extra.shape == (1, 39, VOCAB)
    assert np.abs(np.asarray(extra[0]) - want).max() < F32_TOL
    assert np.abs(np.asarray(main[0]) - want_main).max() < F32_TOL
    assert rms(want - want_main[:-1]) > 0.5 * want.std()


# ------------------------------------------------------------- the engine


@pytest.fixture(scope="module")
def engine_run(f32):
    """A real engine, 4 slots, chunked prefill on: ten requests whose
    prompts are 1 to 6 windows long, offered in two bursts."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import GenerativeEngine

    model, params = f32
    reg = MetricsRegistry()
    engine = GenerativeEngine(
        decode_fns(model), params, max_batch_size=4, prefill_chunk_pages=1,
        registry=reg)
    lengths = [37, 48, 44, 1, 96, 33, 17, 80, 95, 50]
    budgets = [64, 40, 30, 40, 12, 9, 25, 31, 8, 20]
    prompts = [prompt(100 + i, n) for i, n in enumerate(lengths)]
    try:
        engine.warm()
        handles = []
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            handles.append(engine.submit_nowait(p, max_new_tokens=m))
            if i == 4:
                handles[0].wait(120.0)
        outs = [h.wait(120.0) for h in handles]
    finally:
        engine.close()
    return engine, reg, prompts, budgets, outs


@pytest.mark.parametrize("i", range(10))
def test_engine_serves_what_the_reference_would(f32, engine_run, i):
    """Through the scheduler, the arena, insert, move and clear: every
    served token is the reference's best at its position, or within the
    tolerance of it, and the stream is the one the same row gives alone."""
    model, params = f32
    engine, _, prompts, budgets, outs = engine_run
    served = np.asarray(outs[i])
    assert len(served) == budgets[i]
    n = len(prompts[i])
    want = reference_logits(
        params, np.concatenate([prompts[i], served]))[n - 1:-1]
    picked = want[np.arange(len(served)), served]
    assert (want.max(-1) - picked).max() < F32_TOL
    alone, _ = through_the_cache(
        params, decode_fns(model), prompts[i], budgets[i])
    assert served.tolist() == alone.tolist()


def test_engine_counts_latent_bytes_and_expert_assignments(engine_run):
    engine, reg, prompts, budgets, _ = engine_run
    get = lambda name, *lab: reg.get(name).labels("0", *lab).get()
    windows = sum(-(-len(p) // WINDOW) for p in prompts)
    assert get("serving_decode_prefill_windows_total") == windows
    assert get("serving_decode_engine_phase_total", "prefill.window") \
        == windows
    assert get("serving_decode_engine_phase_total", "insert") == 10
    assert engine.compiles_after_warm == 0
    # a step at position t reads t + 1 rows of ROW numbers in each layer
    fed = [
        t for p, m in zip(prompts, budgets)
        for t in range(len(p), len(p) + m - 1)]
    assert get("serving_decode_cache_read_bytes_total", "latent") == sum(
        t + 1 for t in fed) * 3 * ROW * 4
    # 8 of 32 experts held, 8 choices a token, 2 expert layers: 4 a token
    # on average, never more than 16
    picked = get("serving_decode_expert_assignments_total")
    assert 0.5 * 4 * len(fed) < picked < 1.5 * 4 * len(fed)
    steps = get("serving_decode_expert_load_ratio_count")
    assert 0 < steps <= get("serving_decode_steps_total")
    ratio = get("serving_decode_expert_load_ratio_sum") / steps
    assert 1.0 <= ratio <= HELD


def test_engine_counts_the_key_blocks_the_kernel_fetches(engine_run):
    """Beside the valid latents, the span: whole key blocks up to the one
    that holds a row's position.  160 positions are one block of the
    kernel's (cut at the array's end), so every fed row is handed all 160
    in each layer, whatever its depth."""
    from tpu_pipelines.ops.flash_attention import latent_block

    _, reg, prompts, budgets, _ = engine_run
    get = lambda name: reg.get(name).labels("0", "latent").get()
    assert latent_block(MAX_IN + MAX_OUT) == 256
    fed = sum(m - 1 for m in budgets)
    span = get("serving_decode_cache_span_bytes_total")
    assert span == fed * (MAX_IN + MAX_OUT) * 3 * ROW * 4
    valid = get("serving_decode_cache_read_bytes_total")
    assert 0.2 * span < valid < 0.8 * span


def test_kv_bucket_and_pages_count_the_prompt(f32):
    """``page_size`` 16: the step of a row that holds a prompt of 40 and
    ``held`` tokens runs in a bucket of at least ``40 + held`` positions
    (the emitted tokens alone would choose 16 and read a cache cut short
    of the prompt), and the pages in use count the prompt's."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import GenerativeEngine

    model, params = f32
    reg = MetricsRegistry()
    fns = decode_fns(model)
    engine = GenerativeEngine(
        fns, params, max_batch_size=2, page_size=16, registry=reg)
    assert engine.kv_buckets == [16, 32, 64, 128, MAX_IN + MAX_OUT]
    seen = []
    inner = engine._step_for
    tokens = prompt(5, 40)
    try:
        engine.warm()
        engine._step_for = lambda b, kv: (
            seen.append((kv, 40 + engine._slots[0].held)), inner(b, kv))[1]
        served = engine.submit(tokens, max_new_tokens=30)
    finally:
        engine.close()
    assert seen and all(kv >= depth for kv, depth in seen)
    assert {kv for kv, _ in seen} == {64, 128}
    assert seen[-1][1] == 40 + 29
    assert reg.get("serving_decode_cache_pages_in_use").labels(
        "0").get() == -(-(40 + 29) // 16)
    alone, _ = through_the_cache(params, fns, tokens, 30)
    assert np.asarray(served).tolist() == alone.tolist()
    assert engine.compiles_after_warm == 0


def test_the_contract_states_what_the_engine_may_not_guess(f32):
    import jax

    from tpu_pipelines.serving.generative import GenerativeEngine

    model, params = f32
    fns = decode_fns(model)
    cache = fns.blank_cache(3)
    kinds = {
        fns.cache_kind_of(p) for p, _ in
        jax.tree_util.tree_flatten_with_path(cache)[0]}
    assert kinds == set(fns.cache_kinds) == {"latent"}
    kind = fns.cache_kinds["latent"]
    assert kind.by_position and kind.written and kind.in_place
    shapes = {x.shape for x in jax.tree_util.tree_leaves(cache)}
    assert shapes == {(3, MAX_IN + MAX_OUT, ROW)}
    assert fns.cache_positions == MAX_IN + MAX_OUT
    assert fns.step_tally_len == 2 * HELD
    assert int(fns.first_decode_pos(np.array([[1, 1, 1, 0, 0]]))) == 3
    assert fns.prefill is None
    # whole windows over the prompt lie inside the row
    assert decode_fns(model, max_input_len=90, max_decode_len=2
                      ).cache_positions == 96
    with pytest.raises(ValueError, match="prefilled by window"):
        GenerativeEngine(fns, params, prefix_cache_entries=2)
    with pytest.raises(ValueError, match="inside the router"):
        build(expert_offset=30)
