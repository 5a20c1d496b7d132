"""Xing4.0-29B-A4B (models/xing.py) against its plain reference
(benchmark/reference/xing.py) on seeded weights, at a small size on the
CPU: the whole-sequence forward, prefill by window and then decode through
the latent cache, the absorbed form under the YaRN scale, the stream
mixing's Sinkhorn normalisation, the expert layer's shares with the
selection bias in place, the prediction module, the same through a real
``GenerativeEngine``, and the shared classes unchanged where the
configuration has neither scaled positions nor a bias.

Size: 3 layers (1 dense, 2 with experts), d_model 64, 4 heads, latents of
16 + 8, 8 experts of width 32 with 2 a token, all held, 4 streams, windows
of 16, context 96 + 64; rotary theta 100 under YaRN of factor 4 over 32
original positions, whose ramp over the 4 rotary pairs is 0, 0.5, 1, 1:
one pair keeps its frequency, one is blended, two are divided by 4.
Weights from benchmark/weights.py with the spreads of the router, of the
query / key expansions and of the mixing's biases raised, so that the two
chosen are not a matter of rounding, attention is peaked, and ``H_res`` is
far from both the identity and the uniform matrix.

Tolerances.  The program in float32 and the reference compute the same
function in another order of summation (windows, the absorbed form, rows
sorted by expert against a masked loop, the mixing with the tokens last),
so their logits differ by float32 rounding: observed 0.7e-5 to 6e-5 at a
logit spread of 1; ``F32_TOL`` 3e-4 leaves most of a decade for other
seeds.  In bfloat16 (the served precision) over 48 decoded positions the
root mean square of the logits' error read 0.043, 0.097 and 0.085 of their
spread on seeds 7, 8 and 9 (a token whose third expert lies within a
bfloat16 step of its second changes one of two experts; other spreads of
the weights read up to 0.22); ``BF16_TOL`` 0.2 is twice the largest.
Each term left out (``LEFT_OUT``) fails ``F32_TOL`` a thousand times
over, and moves the logits, or where the final norm hides it the merged
stream, by 0.47 to 0.84 of their spread, over twice ``BF16_TOL``; one
Sinkhorn iteration for 20 moves them by 0.25, over ``BF16_TOL`` once.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.generative

VOCAB, WINDOW, EXPERTS, TOP_K, STREAMS = 96, 16, 8, 2, 4
YARN = dict(type="yarn", factor=4.0, original_max_position_embeddings=32,
            beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
HP = dict(
    vocab_size=VOCAB, d_model=64, n_layers=3, n_dense_layers=1, n_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, d_ff=96, d_expert=32,
    n_experts=EXPERTS, experts_held=EXPERTS, expert_offset=0,
    experts_per_token=TOP_K, n_mtp=0, rope_theta=100.0, rope_scaling=YARN,
)
# the reference's keywords for the same model
SHAPE = dict(
    theta=100.0, top_k=TOP_K, yarn=dict(
        factor=4.0, original=32, beta_fast=32, beta_slow=1, mscale=1,
        mscale_all_dim=1))
RULES = {
    "embed/embedding": 0.3, "router": 1.0, "q_up": 0.3, "k_up": 0.3,
    "experts_gate": 0.125, "experts_up": 0.125, "experts_down": 0.177,
    "phi": 0.05, "_alpha": "around_one", "b_pre": 0.5, "b_post": 0.5,
    "b_res": 2.5, "e_score_correction_bias": 0.15,
    "scale": "around_one", "kernel": "fan_in", "v_up": "fan_in",
    "head": "fan_in",
}
F32_TOL, BF16_TOL = 3e-4, 0.2
rms = lambda e: float(np.sqrt(np.mean(np.square(e))))
MAX_IN, MAX_OUT = 96, 64
ROW = 16 + 8                 # numbers a cached position holds in a layer


def build(dtype="float32", seed=7, **over):
    import jax

    from benchmark import weights
    from tpu_pipelines.models import xing

    model = xing.build_xing_model(
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
    from benchmark.reference import xing as ref

    return ref.from_served_tree(weights.flat_leaves(params), n_layers)


REFERENCE_PASS = {}


def reference_pass(params, tokens, what="logits"):
    """[len, vocab] (or the merged stream [len, d_model]) for one
    sequence.  The pass is causal, so the sequence is padded to a multiple
    of 32 and the pass compiled once a length."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import xing as ref

    if what not in REFERENCE_PASS:
        REFERENCE_PASS[what] = jax.jit({
            "logits": lambda rp, t: ref.head_logits(
                rp, ref.hidden(rp, t, **SHAPE)),
            "hidden": lambda rp, t: ref.hidden(rp, t, **SHAPE),
        }[what])
    n = len(tokens)
    padded = np.zeros((-(-n // 32) * 32,), np.int32)
    padded[:n] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(REFERENCE_PASS[what](
            reference_params(params), jnp.asarray(padded)))[:n]


reference_logits = reference_pass


def prompt(seed, n):
    return np.random.default_rng(seed).integers(
        2, VOCAB, size=n).astype(np.int32)


DECODE_FNS = {}


def decode_fns(model, **over):
    """The contract of ``model``, made once for each set of keywords, with
    its window and step programs (``fns.jitted``) compiled once too."""
    import jax

    from tpu_pipelines.models.xing import make_continuous_decode_fns

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
# Every case decodes past the 32 original positions.
CASES = [(37, 40), (48, 30), (1, 40), (96, 64)]


@pytest.mark.parametrize("n", [50, 16, 7, 160])
def test_forward_matches_the_reference(f32, n):
    model, params = f32
    tokens = prompt(n, n)
    got = np.asarray(model.apply({"params": params}, {"inputs": tokens[None]}))
    want = reference_logits(params, tokens)
    assert got.shape == (1, n, VOCAB)
    assert np.abs(got[0] - want).max() < F32_TOL
    assert want.std() > 0.5          # the logits are not all alike


def test_the_merged_stream_matches_the_reference(f32):
    """The streams' sum before the final norm, which removes its size:
    what the prediction module reads, and the one place where a sum taken
    for a mean shows."""
    model, params = f32
    tokens = prompt(4, 50)
    got, _ = model.apply({"params": params}, tokens[None], method="hidden")
    want = reference_pass(params, tokens, "hidden")
    assert np.abs(np.asarray(got[0]) - want).max() < F32_TOL * want.std()
    assert want.std() > 1.0


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


@pytest.mark.parametrize("n_prompt,n_new", [(37, 8), (96, 8)])
def test_a_window_in_blocks_is_the_window_with_its_scores_written_out(
        f32, n_prompt, n_new, monkeypatch):
    """Over ``WINDOW_SCORE_BYTES`` of float32 scores (the cell's 32 heads
    x 1,024 x 16,384) the window attends in the absorbed form through
    ``grouped_attention``, block by block up to its own positions: the
    same cache rows and the same logits as the expanded form the small
    sizes take, and as the reference."""
    from tpu_pipelines.models import pangu_moe

    model, params = f32
    tokens = prompt(n_prompt, n_prompt)
    plain_cache, plain = prefill(decode_fns(model), params, tokens)
    monkeypatch.setattr(pangu_moe, "WINDOW_SCORE_BYTES", 0)
    fns = decode_fns(model, eos_id=VOCAB + 1)     # its own compiled programs
    cache, logits = prefill(fns, params, tokens)
    assert np.abs(np.asarray(logits) - np.asarray(plain)).max() < F32_TOL
    for layer in cache:
        a, b = (np.asarray(c[layer]["latent"])[0, :n_prompt]
                for c in (cache, plain_cache))
        assert np.abs(a - b).max() < F32_TOL
    served, got = through_the_cache(params, fns, tokens, n_new)
    want = reference_logits(
        params, np.concatenate([tokens, served]))[n_prompt - 1:-1]
    assert np.abs(got - want).max() < F32_TOL


def test_absorbed_attention_is_the_expanded_attention(f32):
    """One function, two paths, under the YaRN frequencies and the
    softmax scale times ``mscale ** 2``: the decode step's form over the
    latents themselves against keys and values expanded per head."""
    import jax.numpy as jnp

    from tpu_pipelines.models import pangu_moe

    model, params = f32
    assert model.cfg.rope_scaling.factor == 4.0
    attn = pangu_moe.LatentAttention(model.cfg)
    p = {"params": params["layer_1"]["attn"]}
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(5, 80, 64)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(80), (5, 80))
    q_n, q_r, rows = attn.apply(p, x, pos, method="project")
    depth = jnp.asarray([79, 0, 17, 35, 60])
    ok = jnp.arange(80)[None, :] <= depth[:, None]
    take = lambda q: q[jnp.arange(5), depth]
    one = attn.apply(p, take(q_n), take(q_r), rows, depth, 80,
                     method="absorbed")
    two = attn.apply(
        p, take(q_n)[:, None], take(q_r)[:, None], rows, ok[:, None],
        method="expanded")[:, 0]
    assert np.abs(np.asarray(one) - np.asarray(two)).max() < 1e-5
    assert np.asarray(two).std() > 0.1
    assert pangu_moe.softmax_scale(model.cfg) == pytest.approx(
        24 ** -0.5 * (0.1 * np.log(4.0) + 1) ** 2)


def test_served_precision_stays_near_the_reference():
    """bfloat16 weights, products and cache, as served; the streams and
    their mixing float32."""
    model, params = build("bfloat16")
    tokens = prompt(3, 37)
    served, logits = through_the_cache(
        params, decode_fns(model), tokens, 48)
    want = reference_logits(params, np.concatenate([tokens, served]))
    want = want[36:-1]
    assert rms(logits - want) < BF16_TOL * want.std()


# ------------------------------------------------------- the stream mixing


def mix_of(logits, scale=1.0):
    """``H_res`` of ``StreamMix`` for each ``b_res`` of ``logits``
    [m, 4, 4] (``phi`` 0: the token adds nothing), [m, 4, 4]."""
    import jax
    import jax.numpy as jnp

    from tpu_pipelines.models import xing

    cfg = xing.build_xing_model(
        {**HP, "compute_dtype": "float32", "param_dtype": "float32"}).cfg
    mix = xing.StreamMix(cfg, "mlp")
    x = jnp.ones((1, STREAMS, 64), jnp.float32)
    p = jax.tree_util.tree_map(
        jnp.zeros_like, mix.init(jax.random.key(0), x, method="coefficients")
        ["params"])

    def one(b):
        return mix.apply(
            {"params": {**p, "b_res": b}}, x, method="coefficients")[2][..., 0]

    return np.asarray(jax.vmap(one)(jnp.asarray(logits, jnp.float32)))


# What 20 iterations reach depends on the matrix.  The last normalisation
# is the columns', so their sums are 1 to rounding whatever went in; the
# rows' sums are within 1e-4 of 1 where the logits spread by about 1 (what
# a token moves them by in the cell), and are NOT where a few entries
# tower over their rows and columns (logits at +-30: a support that is
# nearly triangular converges like 1 / iterations).  Both are the
# function the reference states: 20 iterations, not convergence.
@pytest.mark.parametrize("draw", ["spread_1", "at_the_clamps", "beyond"])
def test_sinkhorn_gives_column_sums_of_one_and_finite_entries(draw):
    rng = np.random.default_rng(5)
    logits = {
        "spread_1": rng.normal(0.0, 0.5, (256, 4, 4)),
        "at_the_clamps": rng.choice([-30.0, 30.0], (256, 4, 4)),
        # without the clip exp(100) is inf in float32 and the matrix NaN
        "beyond": rng.choice([-100.0, 100.0], (256, 4, 4)),
    }[draw]
    m = mix_of(logits)
    assert np.isfinite(m).all() and m.min() >= 0.0
    assert np.abs(m.sum(1) - 1.0).max() < 1e-4          # columns
    assert m.max() <= 1.0 + 1e-4
    if draw == "spread_1":
        assert np.abs(m.sum(2) - 1.0).max() < 1e-4      # rows
        assert m.std() > 0.05                           # and not uniform
    else:
        with np.errstate(over="ignore"):
            assert np.isinf(np.exp(np.float32(100.0)))
        # rows of a permutation pattern are exact, the others need not be
        perm = np.broadcast_to(np.where(np.eye(4) > 0, 30.0, -30.0),
                               (1, 4, 4))
        assert np.abs(mix_of(perm) - np.eye(4)).max() < 1e-4


def test_the_mixing_is_the_references(f32):
    """The three coefficients of one sub-layer, tokens last in the
    program and first in the reference."""
    import jax

    from benchmark.reference import xing as ref
    from tpu_pipelines.models import xing

    model, params = f32
    x = np.random.default_rng(2).normal(size=(24, STREAMS, 64)).astype(
        np.float32)
    got = xing.StreamMix(model.cfg, "mlp").apply(
        {"params": params["layer_1"]["ffn_mix"]}, x, method="coefficients")
    with jax.default_matmul_precision("highest"):
        want = ref.mixing(
            reference_params(params), "layer_1/ffn_mix", x, ref.SHAPE)
    for g, w in zip(got, want):
        assert np.abs(np.moveaxis(np.asarray(g), -1, 0) - np.asarray(w)
                      ).max() < 1e-5
    res = np.asarray(want[2])
    assert np.abs(res - 0.25).max() > 0.3              # not uniform
    assert np.abs(res - np.eye(4)).max() > 0.3         # nor the identity
    assert np.abs(res - np.swapaxes(res, 1, 2)).max() > 0.1


# --------------------------------------------------------- terms left out


def _halved_post(next_fun, args, kwargs, context):
    out = next_fun(*args, **kwargs)
    if context.method_name == "coefficients":
        return out[0], out[1] / 2.0, out[2]
    return out


def _mean_for_sum(next_fun, args, kwargs, context):
    out = next_fun(*args, **kwargs)
    return out / STREAMS if context.method_name == "merged" else out


LEFT_OUT = ["selection_bias", "mscale_squared", "yarn_ramp", "post_factor_2",
            "one_sinkhorn_iteration", "streams_averaged"]


@pytest.mark.parametrize("left_out", LEFT_OUT)
def test_the_tolerance_would_notice_a_term_left_out(
        f32, left_out, monkeypatch):
    import jax
    from flax import linen as nn

    from tpu_pipelines.models import pangu_moe, xing

    model, params = f32
    tokens = prompt(9, 80)
    inputs = {"inputs": tokens[None]}
    want = reference_logits(params, tokens)
    good = model.apply({"params": params}, inputs)
    assert np.abs(np.asarray(good[0]) - want).max() < F32_TOL
    run = lambda m=model, p=params: np.asarray(
        m.apply({"params": p}, inputs)[0])
    if left_out == "selection_bias":
        got = run(p=jax.tree_util.tree_map_with_path(
            lambda path, x: x * 0 if "e_score" in str(path[-1]) else x,
            params))
    elif left_out == "mscale_squared":
        monkeypatch.setattr(
            pangu_moe, "softmax_scale", lambda c: (
                c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5)
        got = run()
    elif left_out == "yarn_ramp":
        monkeypatch.setattr(
            pangu_moe, "yarn_inv_freq", lambda d, theta, y: theta ** (
                -np.arange(0, d, 2, dtype=np.float32) / d))
        got = run()
    elif left_out == "post_factor_2":
        with nn.intercept_methods(_halved_post):
            got = run()
    elif left_out == "one_sinkhorn_iteration":
        got = run(m=xing.build_xing_model({
            **HP, "hc_sinkhorn_iters": 1, "compute_dtype": "float32",
            "param_dtype": "float32"}))
    else:
        # The final norm divides the sum's size out again, so the logits
        # cannot see a mean taken for the sum; the merged stream does.
        with nn.intercept_methods(_mean_for_sum):
            got = run()
            assert rms(got - want) < 10 * F32_TOL * want.std()
            got = np.asarray(model.apply(
                {"params": params}, tokens[None], method="hidden")[0][0])
        want = reference_pass(params, tokens, "hidden")
    times = 1 if left_out == "one_sinkhorn_iteration" else 2
    assert rms(got - want) > times * BF16_TOL * want.std()


# ------------------------------------------------------- the expert layer


def expert_layer(cfg_over, layer_params, x):
    from tpu_pipelines.models import pangu_moe, xing

    cfg = xing.build_xing_model(
        {**HP, **cfg_over, "compute_dtype": "float32",
         "param_dtype": "float32"}).cfg
    return pangu_moe.RoutedExperts(cfg).apply({"params": layer_params}, x)


@pytest.fixture(scope="module")
def whole_layer(f32):
    """One expert layer with all 8 experts, and 24 tokens."""
    _, params = f32
    x = np.random.default_rng(11).normal(size=(24, 64)).astype(np.float32)
    return params["layer_1"]["ffn"], x


def _flat(tree, at=()):
    out = {}
    for k, v in tree.items():
        out.update(_flat(v, at + (k,)) if isinstance(v, dict)
                   else {at + (k,): v})
    return out


def reference_layer(layer, x, what="experts"):
    import jax

    from benchmark.reference import xing as ref

    flat = {"ffn/" + "/".join(k): v for k, v in _flat(layer).items()}
    with jax.default_matmul_precision("highest"):
        if what == "shared":
            return np.asarray(ref.gated(flat, "ffn/shared", x, "f32"))
        return np.asarray(ref.experts(
            flat, "ffn", x, "f32", TOP_K, ref.SCALING, 0))


def test_the_shares_of_the_expert_layer_add_up_with_the_bias_in_place(
        whole_layer):
    """Two chips of 4 experts each: what every share gives for its own
    experts, the shared expert counted once, is the uncut reference's
    layer, whose two are the largest of ``sigmoid + bias`` weighed by the
    sigmoids alone; and the bias changes who is chosen."""
    layer, x = whole_layer
    shared = reference_layer(layer, x, "shared")
    total, chosen = shared.copy(), 0
    held = EXPERTS // 2
    for share in range(2):
        cut = slice(share * held, (share + 1) * held)
        part = {**layer, **{
            k: layer[k][cut]
            for k in ("experts_gate", "experts_up", "experts_down")}}
        y, picked = expert_layer(
            {"experts_held": held, "expert_offset": share * held}, part, x)
        assert picked.shape == (24, held)
        chosen += int(np.asarray(picked).sum())
        total += np.asarray(y) - shared
    want = reference_layer(layer, x)
    assert np.abs(total - want).max() < F32_TOL
    assert chosen == 24 * TOP_K
    assert rms(np.asarray(y) - want) > 0.1 * want.std()
    no_bias = {**layer, "e_score_correction_bias": np.zeros(
        EXPERTS, np.float32)}
    _, with_bias = expert_layer({}, layer, x)
    _, without = expert_layer({}, no_bias, x)
    moved = int((np.asarray(with_bias) != np.asarray(without)).any(1).sum())
    assert 2 <= moved < 24
    assert rms(reference_layer(no_bias, x) - want) > 0.1 * want.std()


def test_prediction_module_matches_the_reference():
    """``n_mtp`` 1: the module's logits for token ``t + 2``, and the main
    logits unchanged by its presence."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import xing as ref

    model, params = build(n_mtp=1)
    tokens = prompt(21, 40)
    main, extra = model.apply({"params": params}, {"inputs": tokens[None]})
    rp = reference_params(params)
    assert "mtp_proj/kernel" in rp and "mtp_block/ffn_mix/phi" in rp
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.mtp_logits(rp, jnp.asarray(tokens), **SHAPE))
        want_main = np.asarray(ref.head_logits(
            rp, ref.hidden(rp, jnp.asarray(tokens), **SHAPE)))
    assert extra.shape == (1, 39, VOCAB)
    assert np.abs(np.asarray(extra[0]) - want).max() < F32_TOL
    assert np.abs(np.asarray(main[0]) - want_main).max() < F32_TOL
    assert rms(want - want_main[:-1]) > 0.5 * want.std()


# ------------------------------------------- the shared classes, unchanged


def test_without_scaling_and_bias_the_shared_classes_are_pangus(
        monkeypatch):
    """``rope_scaling`` None and no selection bias: ``LatentAttention``
    and ``RoutedExperts`` under an ``XingConfig`` give, to the last bit,
    what they give under a ``PanguConfig`` of the same sizes, and that is
    what the code before the two options gave: the plain rotary code (the
    frequencies are never asked for) and the plain top ``k``."""
    import jax.numpy as jnp

    from tpu_pipelines.models import evabyte, pangu_moe, xing

    sizes = {k: v for k, v in HP.items() if k != "rope_scaling"}
    plain = xing.build_xing_model({
        **sizes, "rope_scaling": None, "selection_bias": False,
        "compute_dtype": "float32", "param_dtype": "float32"}).cfg
    pangu = pangu_moe.build_pangu_moe_model({
        **sizes, "routed_scaling_factor": 2.0, "rms_norm_eps": 1e-6,
        "compute_dtype": "float32", "param_dtype": "float32"}).cfg
    assert plain.rope_scaling is None and pangu.rope_scaling is None
    assert pangu_moe.softmax_scale(plain) == 24 ** -0.5
    monkeypatch.setattr(pangu_moe, "yarn_inv_freq", None)   # never called
    monkeypatch.setattr(pangu_moe, "yarn_mscale", None)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 40, 64)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(40), (2, 40))
    import jax

    attn = lambda c: pangu_moe.LatentAttention(c)
    p = attn(pangu).init(jax.random.key(1), x, pos, method="full")
    one = attn(plain).apply(p, x, pos, method="full")
    two = attn(pangu).apply(p, x, pos, method="full")
    assert np.array_equal(np.asarray(one), np.asarray(two))
    # the rotary code by the formula it had before ``inv``
    d = 8
    inv = 100.0 ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    q = jnp.asarray(rng.normal(size=(2, 40, 4, d)), jnp.float32)
    old = jnp.concatenate([
        q[..., :4] * cos - q[..., 4:] * sin,
        q[..., 4:] * cos + q[..., :4] * sin], -1)
    assert np.array_equal(
        np.asarray(evabyte.rope(q, pos, 100.0)), np.asarray(old))
    rows = x.reshape(-1, 64)
    ffn = lambda c: pangu_moe.RoutedExperts(c)
    pe = ffn(pangu).init(jax.random.key(2), rows)
    assert "e_score_correction_bias" not in pe["params"]
    (y1, k1), (y2, k2) = ffn(plain).apply(pe, rows), ffn(pangu).apply(pe, rows)
    assert np.array_equal(np.asarray(y1), np.asarray(y2))
    assert np.array_equal(np.asarray(k1), np.asarray(k2))
    assert np.asarray(y1).std() > 0.1


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


def test_engine_counts_latent_bytes_key_blocks_and_experts(engine_run):
    from tpu_pipelines.ops.flash_attention import latent_block

    engine, reg, prompts, budgets, _ = engine_run
    get = lambda name, *lab: reg.get(name).labels("0", *lab).get()
    windows = sum(-(-len(p) // WINDOW) for p in prompts)
    assert get("serving_decode_prefill_windows_total") == windows
    assert get("serving_decode_engine_phase_total", "insert") == 10
    assert engine.compiles_after_warm == 0
    # a step at position t reads t + 1 rows of ROW numbers in each layer
    fed = [
        t for p, m in zip(prompts, budgets)
        for t in range(len(p), len(p) + m - 1)]
    valid = get("serving_decode_cache_read_bytes_total", "latent")
    assert valid == sum(t + 1 for t in fed) * 3 * ROW * 4
    # 160 positions are one key block of the kernel's, cut at the array's
    # end: every fed row is handed all 160 in each layer
    assert latent_block(MAX_IN + MAX_OUT) == 256
    span = get("serving_decode_cache_span_bytes_total", "latent")
    assert span == len(fed) * (MAX_IN + MAX_OUT) * 3 * ROW * 4
    assert 0.2 * span < valid < 0.8 * span
    # every expert is held: 2 choices a token in each of 2 expert layers
    assert get("serving_decode_expert_assignments_total") \
        == len(fed) * TOP_K * 2
    steps = get("serving_decode_expert_load_ratio_count")
    assert 0 < steps <= get("serving_decode_steps_total")
    touched = get("serving_decode_experts_touched_total")
    # a step of up to 4 rows touches 2 to 8 experts a layer, never 16 here
    assert 2 * 2 * steps <= touched < 2 * EXPERTS * steps
    ratio = get("serving_decode_expert_load_ratio_sum") / steps
    assert 1.0 <= ratio <= EXPERTS


def test_the_contract_is_pangus_over_this_model(f32):
    import jax

    from tpu_pipelines.models import pangu_moe, xing

    model, params = f32
    assert xing.make_continuous_decode_fns \
        is pangu_moe.make_continuous_decode_fns
    assert isinstance(model, xing.XingMoE)
    for cls in (pangu_moe.LatentAttention, pangu_moe.RoutedExperts,
                pangu_moe.RMSNorm):
        assert getattr(xing, cls.__name__) is cls
    fns = decode_fns(model)
    cache = fns.blank_cache(3)
    kinds = {
        fns.cache_kind_of(p) for p, _ in
        jax.tree_util.tree_flatten_with_path(cache)[0]}
    assert kinds == set(fns.cache_kinds) == {"latent"}
    assert {x.shape for x in jax.tree_util.tree_leaves(cache)} == {
        (3, MAX_IN + MAX_OUT, ROW)}
    assert fns.step_tally_len == 2 * EXPERTS
    with pytest.raises(ValueError, match="inside the router"):
        build(experts_held=4, expert_offset=6)
    # the published sizes are the defaults
    c = xing.XingConfig()
    assert (c.d_model, c.n_heads, c.n_layers, c.n_dense_layers, c.d_ff,
            c.d_expert, c.n_experts, c.experts_held, c.experts_per_token,
            c.hc_mult, c.hc_sinkhorn_iters, c.vocab_size, c.row_width) == (
        3584, 32, 40, 2, 9216, 1024, 64, 64, 4, 4, 20, 131072, 576)
    assert pangu_moe.softmax_scale(c) == pytest.approx(
        192 ** -0.5 * 1.4159 ** 2, rel=1e-4)
