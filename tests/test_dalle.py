"""DALLE model tests: logits mask, unique pads, loss weighting, and the
big one — KV-cache sampler equivalence vs a reference-style full-forward
sampling loop (SURVEY.md §7 'hard parts')."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu import DALLE, DALLEConfig, VAEConfig
from dalle_pytorch_tpu.models.dalle import generate_codes
from dalle_pytorch_tpu.utils.helpers import top_k_filter

VCFG = VAEConfig(image_size=16, num_tokens=32, codebook_dim=16, num_layers=2,
                 hidden_dim=8)


def build(attn_types=("full",), reversible=False, text_seq_len=6, depth=2):
    cfg = DALLEConfig.from_vae(
        VCFG, dim=32, num_text_tokens=50, text_seq_len=text_seq_len, depth=depth,
        heads=2, dim_head=8, attn_types=attn_types, reversible=reversible)
    dalle = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    text = jax.random.randint(rng, (2, cfg.text_seq_len), 1, 50)
    codes = jax.random.randint(rng, (2, cfg.image_seq_len), 0, 32)
    params = dalle.init(rng, text, codes, return_loss=True)
    return cfg, dalle, params, text, codes


@pytest.fixture(scope="module")
def small():
    return build(attn_types=("full", "axial_row", "axial_col", "conv_like"),
                 depth=4)


def test_logits_mask(small):
    """text positions predict text vocab only; image positions image vocab
    only (ref dalle_pytorch.py:356-367, :480-484)."""
    cfg, dalle, params, text, codes = small
    logits = np.asarray(dalle.apply(params, text, codes))
    n_text_total = cfg.total_text_tokens
    assert logits.shape == (2, cfg.seq_len, cfg.total_tokens)
    assert (logits[:, : cfg.text_seq_len, n_text_total:] < -1e30).all()
    assert (logits[:, cfg.text_seq_len:, :n_text_total] < -1e30).all()
    # unmasked regions finite
    assert np.isfinite(logits[:, : cfg.text_seq_len, :n_text_total]).all()
    assert np.isfinite(logits[:, cfg.text_seq_len:, n_text_total:]).all()


def test_unique_pad_ids(small):
    """pad token 0 at different positions must embed differently
    (ref :315, :440-441): zeroing a pad at position p only affects outputs
    from p on, and two all-pad texts differ from each other's embeddings."""
    cfg, dalle, params, _, codes = small
    t1 = jnp.zeros((1, cfg.text_seq_len), jnp.int32)
    t2 = jnp.full((1, cfg.text_seq_len), 3, jnp.int32)
    l1 = dalle.apply(params, t1, codes[:1])
    l2 = dalle.apply(params, t2, codes[:1])
    assert not np.allclose(np.asarray(l1), np.asarray(l2))


def test_loss_weighting():
    """loss = (text + w*img) / (w+1) (ref :499)."""
    cfg, dalle, params, text, codes = build()

    logits = dalle.apply(params, text, codes)
    logp = jax.nn.log_softmax(logits, axis=-1)
    text_range = np.arange(cfg.text_seq_len) + cfg.total_text_tokens - cfg.text_seq_len
    t = np.asarray(text)
    t_remap = np.where(t == 0, text_range, t)
    labels = np.concatenate([t_remap, np.asarray(codes) + cfg.total_text_tokens], 1)
    ll = np.take_along_axis(np.asarray(logp), labels[:, :, None], axis=2)[..., 0]
    lt = -ll[:, : cfg.text_seq_len].mean()
    li = -ll[:, cfg.text_seq_len:].mean()
    expected = (lt + cfg.loss_img_weight * li) / (cfg.loss_img_weight + 1)

    loss = float(dalle.apply(params, text, codes, return_loss=True))
    assert np.allclose(loss, expected, rtol=1e-5)


def test_top_k_filter_semantics():
    """k = max(int((1-thres)*V), 1) (ref :44-50)."""
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(2, 100)).astype(np.float32))
    k = max(int((1 - 0.9) * 100), 1)  # note: float truncation gives 9, as in the ref
    out = np.asarray(top_k_filter(logits, thres=0.9))
    assert (np.isfinite(out).sum(axis=-1) == k).all()
    out1 = np.asarray(top_k_filter(logits, thres=0.999))
    assert (np.isfinite(out1).sum(axis=-1) == 1).all()
    # kept entries are exactly the k largest, unchanged
    row = np.asarray(logits[0])
    kept = np.where(np.isfinite(out[0]))[0]
    assert set(kept) == set(np.argsort(row)[-k:])


@pytest.mark.slow
@pytest.mark.parametrize("attn_types,reversible", [
    (("full",), False),
    (("full", "axial_row", "axial_col", "conv_like"), False),
    (("sparse",), False),
    (("full",), True),
])
def test_sampler_equivalence_greedy(attn_types, reversible):
    """KV-cache prefill+scan sampler must produce exactly the tokens a
    reference-style full-forward-per-step greedy loop produces."""
    cfg, dalle, params, text, _ = build(attn_types=attn_types,
                                        reversible=reversible,
                                        text_seq_len=5, depth=len(attn_types))

    # greedy: filter_thres leaving k=1 makes categorical deterministic
    thres = 1.0 - 1.0 / cfg.total_tokens
    fast = np.asarray(generate_codes(
        dalle, params, text, jax.random.PRNGKey(0), filter_thres=thres))

    # reference-style loop: full forward each step, argmax of last logits
    out_codes = np.zeros((text.shape[0], 0), np.int32)
    for cur in range(cfg.image_seq_len):
        codes_in = jnp.asarray(out_codes) if cur > 0 else None
        logits = dalle.apply(params, text, codes_in)
        last = np.asarray(logits)[:, -1, :]
        nxt = last.argmax(-1) - cfg.total_text_tokens
        out_codes = np.concatenate([out_codes, nxt[:, None].astype(np.int32)], 1)

    np.testing.assert_array_equal(fast, out_codes,
                                  err_msg=f"{attn_types} reversible={reversible}")


def test_priming(small):
    """Image priming keeps the primed prefix (ref :389-398)."""
    cfg, dalle, params, text, codes = small
    n_prime = int(0.4375 * cfg.image_seq_len)
    prime = codes[:, :n_prime]
    out = np.asarray(generate_codes(dalle, params, text, jax.random.PRNGKey(0),
                                    prime_codes=prime, filter_thres=0.9))
    assert out.shape == (2, cfg.image_seq_len)
    np.testing.assert_array_equal(out[:, :n_prime], np.asarray(prime))


@pytest.mark.slow
def test_grads_flow(small):
    cfg, dalle, params, text, codes = small

    def loss_fn(p):
        return dalle.apply(p, text, codes, return_loss=True)

    g = jax.grad(loss_fn)(params)
    total = jax.tree.reduce(lambda a, x: a + float(jnp.abs(x).sum()), g, 0.0)
    assert np.isfinite(total) and total > 0


def test_top_k_filter_sliced_vs_joint_vocab():
    """The decode path filters image-vocab-only logits with k derived from
    the FULL joint vocab (k_vocab) — including the clamp branch where that
    k exceeds the sliced width. Must select the identical candidate set as
    the reference-style filter over joint-vocab logits whose text half is
    -inf (ref dalle_pytorch.py:44-50, :482-484)."""
    rng = np.random.default_rng(0)
    v_img, v_total = 12, 40
    img_logits = rng.normal(size=(3, v_img)).astype(np.float32)
    joint = np.full((3, v_total), -np.inf, np.float32)
    joint[:, v_total - v_img:] = img_logits

    for thres in (0.5, 0.8, 0.99):  # k = 20 (clamped to 12), 8, 1
        ref = np.asarray(top_k_filter(jnp.asarray(joint), thres=thres))
        fast = np.asarray(top_k_filter(jnp.asarray(img_logits), thres=thres,
                                       k_vocab=v_total))
        np.testing.assert_array_equal(ref[:, v_total - v_img:], fast,
                                      err_msg=f"thres={thres}")


# --- the top-k cut-off by exact selection (PR 31): bit for bit the filter a
# sort gives.  The oracles live here only; the program keeps no sort.

# (k_vocab, thres) as the three generate cells derive k over 8,192 image
# logits: cub200 1601, lucid1024 1844, jamba2-3b 6553
CELL_K = {1601: (16012, 0.9), 1844: (18448, 0.9), 6553: (65536, 0.9)}


def _thres_for(k, width):
    """A threshold at which ``top_k_filter`` keeps exactly ``k`` of
    ``width`` logits."""
    thres = 1.0 - (k + 0.5) / width
    assert max(int((1 - thres) * width), 1) == k
    return thres


def _filter_args(k, width):
    if k in CELL_K and width == 8192:
        k_vocab, thres = CELL_K[k]
        assert int((1 - thres) * k_vocab) == k
        return {"thres": thres, "k_vocab": k_vocab}
    return {"thres": _thres_for(k, width)}


def _logits(content, rows, width, seed=0):
    """f32 rows that try the selection: Gaussian values; heavy ties (a few
    dozen distinct values, so the cut-off is a duplicated value); runs of +0
    and -0; +inf and -inf entries; all-equal rows."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, width)).astype(np.float32)
    if content == "ties":
        x = np.round(x * 4) / 4
    elif content == "zeros":
        x[:, : width // 4] = 0.0
        x[:, width // 4: width // 2] = -0.0
        x = rng.permuted(x, axis=-1)
    elif content == "infs":
        x[:, ::5] = np.inf
        x[:, 1::7] = -np.inf
    elif content == "equal":
        x[:] = rng.standard_normal((rows, 1)).astype(np.float32)
        x[0] = -np.inf
    else:
        assert content == "normal"
    return x


def _oracle_filter(x, k):
    """``np.partition`` finds the k-th largest; the comparison runs on the
    widened values (exact for bf16), the output keeps the input's dtype."""
    wide = np.asarray(x, np.float32)
    kth = np.partition(wide, wide.shape[-1] - k, axis=-1)[
        ..., wide.shape[-1] - k, None]
    return np.where(wide < kth, np.asarray(-np.inf, x.dtype), x)


def _sorting_filter(logits, thres=0.5, k_vocab=None):
    """``top_k_filter`` as it was until PR 31: the cut-off from
    ``lax.top_k``.  The oracle of the sampler tests; the program keeps none."""
    from dalle_pytorch_tpu.utils.helpers import top_k_count

    k = top_k_count(logits.shape[-1], thres, k_vocab)
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, -jnp.inf, logits)


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _assert_bit_equal(got, x, k):
    want = _oracle_filter(np.asarray(x), k)
    assert got.dtype == x.dtype and got.shape == x.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))


SHAPES_K = [(rows, 64, k) for rows in (1, 4, 32, 128) for k in (1, 7, 64)] + [
    (rows, 8192, k) for rows in (1, 4, 32, 128)
    for k in (1, 7, 1601, 1844, 6553, 8192)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("rows,width,k", SHAPES_K)
def test_top_k_filter_bit_equal_to_partition(rows, width, k, dtype):
    """Every shape and k in use (rows 1 to 128; the tests' 64 logits and the
    cells' 8,192; k = 1, 7, the three cells' and the whole row), on rows with
    ties: the filtered logits are the oracle's, bit for bit."""
    x = jnp.asarray(_logits("ties" if k % 2 else "normal", rows, width,
                            seed=k), dtype)
    got = top_k_filter(x, **_filter_args(k, width))
    _assert_bit_equal(got, x, k)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("width,k", [(64, 1), (64, 7), (64, 33), (8192, 7),
                                     (8192, 1601), (8192, 6553)])
@pytest.mark.parametrize("content", ["ties", "zeros", "infs", "equal"])
def test_top_k_filter_bit_equal_on_hard_rows(content, width, k, dtype):
    x = jnp.asarray(_logits(content, 4, width, seed=width + k), dtype)
    got = top_k_filter(x, **_filter_args(k, width))
    _assert_bit_equal(got, x, k)


@pytest.mark.parametrize("width,k", [(64, 7), (8192, 1601)])
@pytest.mark.parametrize("how", ["jit", "vmap", "scan"])
def test_top_k_filter_bit_equal_under_transforms(how, width, k):
    """Under ``jit``, per row under ``vmap`` (the serve tick and the
    speculative sampler) and inside a ``lax.scan`` (``decode_codes``)."""
    x = jnp.asarray(_logits("ties", 8, width, seed=3))
    args = _filter_args(k, width)
    one = lambda a: top_k_filter(a, **args)  # noqa: E731
    if how == "jit":
        got = jax.jit(one)(x)
    elif how == "vmap":
        got = jax.jit(jax.vmap(one))(x)
    else:
        pairs = x.reshape(4, 2, width)
        _, got = jax.lax.scan(lambda c, a: (c, one(a)), 0, pairs)
        got = got.reshape(x.shape)
    _assert_bit_equal(got, x, k)


@pytest.mark.parametrize("top_p", [None, 0.8], ids=["top_k", "top_k+top_p"])
@pytest.mark.parametrize("temperature", [1.0, 0.7])
def test_sample_image_code_traced_temperature_matches_sort_oracle(
        temperature, top_p):
    """The sampler with a traced per-row temperature (the serve tick's):
    the same codes as the same sampler over a ``lax.top_k`` cut-off."""
    from dalle_pytorch_tpu.models.dalle import sample_image_code
    from dalle_pytorch_tpu.utils.helpers import top_p_filter

    x = jnp.asarray(_logits("ties", 16, 64, seed=5))
    keys = jax.random.split(jax.random.PRNGKey(2), 16)
    temps = jnp.full((16,), temperature, jnp.float32)

    def oracle(logits, key, temp):
        filtered = _sorting_filter(logits / temp, thres=0.9, k_vocab=64)
        if top_p is not None:
            filtered = top_p_filter(filtered, top_p)
        return jax.random.categorical(key, filtered).astype(jnp.int32)

    got = jax.jit(jax.vmap(lambda a, key, t: sample_image_code(
        a, key, k_vocab=64, filter_thres=0.9, temperature=t,
        top_p=top_p)))(x, keys, temps)
    want = jax.jit(jax.vmap(oracle))(x, keys, temps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.fixture(scope="module")
def cub200_twin():
    """``cub200-generate``'s tiny twin with a prefill tiled to 4 rows, and a
    threshold that keeps 6 of its 64 logits (the cell's own 0.9 keeps them
    all, so the cut-off would decide nothing)."""
    from benchmark import harness
    from dalle_pytorch_tpu.models.dalle import prefill_codes, tile_prefill

    cell = harness.load_cell("cub200-generate", rehearse=True)
    cfg = harness.build_configs(cell.config)[0]
    model = DALLE(cfg)
    text = jax.random.randint(jax.random.PRNGKey(1), (1, cfg.text_seq_len),
                              1, cfg.num_text_tokens)
    params = model.init(jax.random.PRNGKey(0), text,
                        jnp.zeros((1, cfg.image_seq_len), jnp.int32))
    first, caches = tile_prefill(*prefill_codes(model, params, text), 4)
    thres = _thres_for(6, cfg.total_tokens)

    def draw(key):
        from dalle_pytorch_tpu.models.dalle import decode_codes

        # a new jit each call: the sampler is traced again
        return np.asarray(jax.jit(lambda p, f, c, k: decode_codes(
            model, p, f, c, k, filter_thres=thres))(
            params, first, caches, key))

    return cfg, draw


def test_decode_codes_draws_the_sort_oracles_codes(cub200_twin, monkeypatch):
    """With one key, ``decode_codes`` over the selection draws exactly the
    codes it draws over a sort-based cut-off."""
    from dalle_pytorch_tpu.models import dalle as dalle_module

    cfg, draw = cub200_twin
    selected = draw(jax.random.PRNGKey(31))
    monkeypatch.setattr(dalle_module, "top_k_filter", _sorting_filter)
    np.testing.assert_array_equal(selected, draw(jax.random.PRNGKey(31)))
    assert selected.shape == (4, cfg.image_seq_len)
    assert len(np.unique(selected)) > 4  # a draw, not a constant


def test_sampler_trace_reports_its_top_k(cub200_twin, tmp_path):
    """One trace of ``decode_codes`` holds two samplers (the first code's and
    the scan body's): a ``sample.top_k`` record each, the two gauges, and the
    line ``tools/obs_report.py`` prints under ``-- decode --``."""
    from dalle_pytorch_tpu.obs import metrics, telemetry
    from dalle_pytorch_tpu.obs.report import build_report, render_text

    _, draw = cub200_twin
    reg = metrics.init()
    tel = telemetry.init(tmp_path, run_id="top-k")
    try:
        draw(jax.random.PRNGKey(0))
        rendered = reg.render()
    finally:
        telemetry.shutdown()
        metrics.shutdown()
    events = telemetry.read_events(tel.path)
    records = [{f: e[f] for f in ("rows", "vocab", "k", "passes", "method")}
               for e in events
               if e["kind"] == "sample" and e["name"] == "top_k"]
    assert records == 2 * [{"rows": 4, "vocab": 64, "k": 6, "passes": 32,
                            "method": "select"}]
    assert "graft_sample_topk_k 6" in rendered
    assert "graft_sample_topk_passes 32" in rendered
    report = build_report(events)
    assert report["sampler"] == {"traces": 2, **records[-1]}
    text_report = render_text(report)
    assert "-- decode --" in text_report
    assert ("sampler top-k: keeps 6 of 64 logits, cut-off by select in 32 "
            "passes (4 rows; last of 2 sampler traces)") in text_report


def test_top_p_filter_semantics():
    from dalle_pytorch_tpu.utils.helpers import top_p_filter

    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    out = np.asarray(top_p_filter(logits, 0.75))  # 0.5+0.3 crosses 0.75
    assert np.isfinite(out[0, :2]).all() and np.isinf(out[0, 2:]).all()
    out1 = np.asarray(top_p_filter(logits, 0.4))  # top token always kept
    assert np.isfinite(out1[0, 0]) and np.isinf(out1[0, 1:]).all()
    # p=1 keeps everything
    assert np.isfinite(np.asarray(top_p_filter(logits, 1.0))).all()
    # order-invariant: permuting the vocab permutes the mask identically
    perm = np.asarray([2, 0, 3, 1])
    out_p = np.asarray(top_p_filter(logits[:, perm], 0.75))
    np.testing.assert_array_equal(np.isfinite(out_p[0]),
                                  np.isfinite(out[0])[perm])


def test_generate_with_top_p(small):
    """Nucleus sampling runs inside the jitted decode scan and yields valid
    image codes; p=1.0 (keep all) matches plain top-k sampling exactly."""
    cfg, dalle, params, text, codes = small
    out = np.asarray(generate_codes(dalle, params, text, jax.random.PRNGKey(0),
                                    filter_thres=0.9, top_p=0.9))
    assert out.shape == (2, cfg.image_seq_len)
    assert (out >= 0).all() and (out < cfg.num_image_tokens).all()

    plain = np.asarray(generate_codes(dalle, params, text,
                                      jax.random.PRNGKey(0), filter_thres=0.9))
    full = np.asarray(generate_codes(dalle, params, text,
                                     jax.random.PRNGKey(0), filter_thres=0.9,
                                     top_p=1.0))
    np.testing.assert_array_equal(plain, full)


def test_tile_prefill_matches_batched_prefill(small):
    """Shared prompt prefill: prefilling ONE row and tiling the state
    (models.dalle.tile_prefill) must agree with prefilling the repeated
    prompt at full batch — logits and every layer's caches.

    What is claimed is agreement to one ulp of the cache's STORAGE dtype,
    not bitwise equality: XLA compiles the batch-1 and the batch-3 forward
    as different programs, so their f32 k/v differ in the last bits
    (~1e-7), and where such a pair straddles a bf16 rounding boundary the
    stored values land on adjacent bf16 numbers.  One bf16 ulp is at most
    2**-7 of the value (8 significand bits); anything beyond that, or
    more than a stray handful of such elements, is a real divergence.
    The f32 logits are held to the f32-sized 1e-5."""
    from dalle_pytorch_tpu.models.dalle import prefill_codes, tile_prefill

    cfg, dalle, params, text, _ = small
    reps = 3
    text_rep = jnp.repeat(text[:1], reps, axis=0)

    fl1, c1 = prefill_codes(dalle, params, text[:1])
    flt, ct = tile_prefill(fl1, c1, reps)
    fln, cn = prefill_codes(dalle, params, text_rep)

    np.testing.assert_allclose(np.asarray(flt), np.asarray(fln),
                               rtol=1e-5, atol=1e-5)
    assert len(ct) == len(cn)
    for tiled, batched in zip(ct, cn):
        for t, n in zip(tiled, batched):
            assert t.shape == n.shape and t.dtype == n.dtype
            t32, n32 = np.asarray(t, np.float32), np.asarray(n, np.float32)
            if t.dtype == jnp.bfloat16:
                np.testing.assert_allclose(t32, n32, rtol=2.0 ** -7,
                                           atol=1e-6)
                assert (t32 != n32).mean() < 0.01
            else:
                np.testing.assert_allclose(t32, n32, rtol=1e-5, atol=1e-5)

    with pytest.raises(AssertionError):  # batch>1 prefills cannot be tiled
        tile_prefill(fln, cn, 2)


def test_split_sampler_composition_matches_generate_codes(small):
    """prefill_codes + decode_codes (the split the shared-prefill path
    uses) must reproduce generate_codes exactly for the same rng."""
    from dalle_pytorch_tpu.models.dalle import (decode_codes, prefill_codes,
                                                tile_prefill)

    cfg, dalle, params, text, _ = small
    rng = jax.random.PRNGKey(7)
    whole = np.asarray(generate_codes(dalle, params, text, rng,
                                      filter_thres=0.9))
    fl, caches = prefill_codes(dalle, params, text)
    split = np.asarray(decode_codes(dalle, params, fl, caches, rng,
                                    filter_thres=0.9))
    np.testing.assert_array_equal(whole, split)

    # and through a tiled batch-1 prefill of a repeated prompt: greedy
    # decode must equal the per-row generate_codes greedy output
    thres = 1.0 - 1.0 / cfg.total_tokens
    text_rep = jnp.repeat(text[:1], 2, axis=0)
    ref = np.asarray(generate_codes(dalle, params, text_rep,
                                    jax.random.PRNGKey(0),
                                    filter_thres=thres))
    fl1, c1 = prefill_codes(dalle, params, text[:1])
    flt, ct = tile_prefill(fl1, c1, 2)
    tiled = np.asarray(decode_codes(dalle, params, flt, ct,
                                    jax.random.PRNGKey(0),
                                    filter_thres=thres))
    np.testing.assert_array_equal(ref, tiled)


def test_generate_chunked_shared_prefill(small, monkeypatch):
    """cli.generate_chunked with a repeated prompt must prefill ONCE
    (shared-prefill path, tiled caches) and never call the per-chunk
    generate_codes; distinct prompts keep the per-chunk path."""
    from dalle_pytorch_tpu import cli

    cfg, dalle, params, text, _ = small
    calls = {"prefill": 0, "full": 0}
    real_prefill, real_gen = cli.prefill_codes, cli.generate_codes

    def counting_prefill(*a, **k):
        calls["prefill"] += 1
        return real_prefill(*a, **k)

    def counting_gen(*a, **k):
        calls["full"] += 1
        return real_gen(*a, **k)

    monkeypatch.setattr(cli, "prefill_codes", counting_prefill)
    monkeypatch.setattr(cli, "generate_codes", counting_gen)

    def decode(codes):
        return jnp.zeros((codes.shape[0], 4, 4, 3))

    tokens = np.repeat(np.asarray(text[:1]), 5, axis=0)
    images, rng = cli.generate_chunked(
        dalle, params["params"], decode, tokens, batch_size=2, top_k=0.9,
        rng=jax.random.PRNGKey(0))
    assert images.shape[0] == 5
    assert calls == {"prefill": 1, "full": 0}

    tokens2 = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (3, cfg.text_seq_len), 1, 50))
    images2, _ = cli.generate_chunked(
        dalle, params["params"], decode, tokens2, batch_size=2, top_k=0.9,
        rng=rng)
    assert images2.shape[0] == 3
    assert calls["full"] == 2  # two padded chunks, no shared prefill


def test_phase_head_init_call_path_independent():
    """Initializing through a phase-only head caller (prefill computes only
    image-phase logits) must still create BOTH phase kernels — otherwise a
    model first used for generation couldn't load a full training
    checkpoint (param tree mismatch on the missing phase)."""
    cfg, dalle, params, text, _ = build()
    pre_params = dalle.init(jax.random.PRNGKey(0), text,
                            method=DALLE.prefill)
    full_head = params["params"]["to_logits_dense"]
    pre_head = pre_params["params"]["to_logits_dense"]
    assert set(pre_head) == set(full_head) == {
        "text_kernel", "text_bias", "image_kernel", "image_bias"}
    for k in full_head:
        assert pre_head[k].shape == full_head[k].shape, k


def test_env_flag_semantics(monkeypatch):
    """Boolean env knobs must be OFF-able: X=0/false/no/off (any case)
    parse as False; bool(os.environ.get(X)) treats '0' as ON."""
    from dalle_pytorch_tpu.utils.helpers import env_flag

    monkeypatch.delenv("X_FLAG", raising=False)
    assert env_flag("X_FLAG") is False
    assert env_flag("X_FLAG", default=True) is True
    for off in ("0", "false", "no", "off", "", "False", " 0 ", "OFF"):
        monkeypatch.setenv("X_FLAG", off)
        assert env_flag("X_FLAG") is False, repr(off)
        assert env_flag("X_FLAG", default=True) is False, repr(off)
    for on in ("1", "true", "yes", "512", "on"):
        monkeypatch.setenv("X_FLAG", on)
        assert env_flag("X_FLAG") is True, repr(on)


#: what checkpoints written before the seven execution switches were
#: retired carry in their hparams, at the values every one of them had
RETIRED_HPARAMS = dict(use_pallas=False, pallas_block_q=128,
                       pallas_block_k=128, logits_bf16=False,
                       onehot_embed=False, head_phase_sliced=True,
                       sliced_kv_decode=True)


@pytest.mark.parametrize("key", sorted(RETIRED_HPARAMS))
def test_from_dict_drops_a_retired_switch(key):
    """Hparams are input from outside the program: an older checkpoint's
    carry a retired switch, and ``from_dict`` must build the same config
    as without it instead of failing in ``cls(**d)``."""
    cfg = DALLEConfig(dim=32, attn_types=("full", "axial_row"))
    assert not hasattr(cfg, key)
    old = {**cfg.to_dict(), key: RETIRED_HPARAMS[key]}
    assert DALLEConfig.from_dict(old) == DALLEConfig.from_dict(cfg.to_dict())
    with pytest.raises(TypeError):      # an unknown key still fails
        DALLEConfig.from_dict({**cfg.to_dict(), "no_such_field": 1})


def test_checkpoint_with_every_retired_switch_loads_and_generates(tmp_path):
    from dalle_pytorch_tpu import DiscreteVAE
    from dalle_pytorch_tpu.cli import load_dalle_checkpoint
    from dalle_pytorch_tpu.utils.checkpoint import save_checkpoint

    cfg, dalle, params, text, _ = build(
        attn_types=("full", "axial_row", "axial_col", "conv_like"), depth=4)
    vae_params = DiscreteVAE(VCFG).init(
        {"params": jax.random.PRNGKey(1), "gumbel": jax.random.PRNGKey(1)},
        jnp.zeros((1, 16, 16, 3)))["params"]
    path = tmp_path / "old.pt"
    save_checkpoint(path, {
        "hparams": {**cfg.to_dict(), **RETIRED_HPARAMS},
        "vae_params": VCFG.to_dict(), "vae_weights": vae_params,
        "weights": params["params"]})
    dalle2, cfg2, params2, _, _ = load_dalle_checkpoint(path)
    assert cfg2 == cfg
    thres = 1.0 - 1.0 / cfg.total_tokens  # greedy: k=1
    want = generate_codes(dalle, params, text, jax.random.PRNGKey(0),
                          filter_thres=thres)
    got = generate_codes(dalle2, {"params": params2}, text,
                         jax.random.PRNGKey(0), filter_thres=thres)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


#: ``DALLEConfig``'s fields that select how the same parameters are
#: computed and not what the model is (ROADMAP D4 counts them; ``dtype``
#: apart).  A new one is an option: it needs two callers that differ.
EXECUTION_FIELDS = {
    "use_remat", "ff_expert_dispatch", "ff_expert_capacity_factor",
    "ring_axis", "sp_impl", "sp_size", "kv_cache_bf16", "kv_cache_int8",
    "weights_int8", "aligned_span_decode"}

MODEL_FIELDS = {
    "dim", "num_text_tokens", "text_seq_len", "depth", "heads", "dim_head",
    "reversible", "attn_dropout", "ff_dropout", "sparse_attn", "attn_types",
    "loss_img_weight", "num_image_tokens", "image_size", "image_fmap_size",
    "ff_experts", "ff_expert_top_k", "ff_aux_weight", "trunk"}


def test_execution_fields_are_exactly_the_ten():
    import dataclasses

    names = {f.name for f in dataclasses.fields(DALLEConfig)}
    assert names - MODEL_FIELDS - {"dtype"} == EXECUTION_FIELDS
    assert len(EXECUTION_FIELDS) == 10
    # every plan field is an execution field; use_remat is the one that a
    # checkpoint still records
    assert set(DALLEConfig._PLAN_FIELDS) == EXECUTION_FIELDS - {"use_remat"}
    assert not set(DALLEConfig._RETIRED_FIELDS) & names


def test_cub200_preset_is_the_benchmarks_configuration():
    """``presets.cub200_config`` (the tests' and ``chip_smoke.py``'s CUB-200
    model) against the benchmark's ``cub200`` configuration: every
    hyperparameter of its ``dalle`` section and the geometry its ``vae``
    section implies.  The one allowed difference is the text vocabulary:
    the preset keeps the 7800 of the BPE file's name, the benchmark the
    7740 entries the file holds."""
    import json
    from pathlib import Path

    from dalle_pytorch_tpu.presets import cub200_config

    conf = json.loads((Path(__file__).resolve().parent.parent / "benchmark"
                       / "configs" / "cub200.json").read_text())
    cfg = cub200_config()
    want = dict(conf["dalle"], attn_types=tuple(conf["dalle"]["attn_types"]))
    differ = {k for k, v in want.items() if getattr(cfg, k) != v}
    assert differ == {"num_text_tokens"}
    assert (cfg.num_text_tokens, want["num_text_tokens"]) == (7800, 7740)
    vae = conf["vae"]
    assert cfg.num_image_tokens == vae["num_tokens"]
    assert cfg.image_fmap_size == vae["image_size"] // 2 ** vae["num_layers"]
    assert jnp.dtype(cfg.dtype).name == conf["dtype"]
