"""End-to-end CLI smoke tests: train_vae -> train_dalle (+resume) ->
generate -> genrank on tiny synthetic data.

Covers the reference's L5 entry-point surface (SURVEY.md §1, §5.6) the way
its rainbow notebook covered the models (SURVEY.md §4): tiny shapes, few
steps, real end-to-end wiring including checkpoints, logs, sampling, and
output files.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

VOCAB_WORDS = ["red", "green", "blue", "yellow", "circle", "square", "bird",
               "a", "the", "of"]


@pytest.fixture(scope="module")
def tiny_tokenizer_json(tmp_path_factory):
    """A tiny word-level HF tokenizer json for HugTokenizer."""
    from tokenizers import Tokenizer, models, pre_tokenizers

    vocab = {"[UNK]": 0}
    for w in VOCAB_WORDS:
        vocab[w] = len(vocab)
    tok = Tokenizer(models.WordLevel(vocab, unk_token="[UNK]"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    path = tmp_path_factory.mktemp("tok") / "tiny_tokenizer.json"
    tok.save(str(path))
    return path


@pytest.fixture(scope="module")
def tiny_dataset(tmp_path_factory):
    """12 random 24x24 images + caption txt files, stem-paired."""
    rng = np.random.default_rng(0)
    folder = tmp_path_factory.mktemp("data")
    from PIL import Image

    for i in range(12):
        img = (rng.uniform(size=(24, 24, 3)) * 255).astype(np.uint8)
        Image.fromarray(img).save(folder / f"sample_{i}.png")
        words = rng.choice(VOCAB_WORDS, size=3, replace=True)
        (folder / f"sample_{i}.txt").write_text(" ".join(words) + "\n")
    return folder


VAE_HPARAMS = dict(EPOCHS=1, BATCH_SIZE=4, NUM_TOKENS=32, NUM_LAYERS=2,
                   NUM_RESNET_BLOCKS=0, EMB_DIM=16, HID_DIM=16)
DALLE_HPARAMS = dict(BATCH_SIZE=4, MODEL_DIM=32, TEXT_SEQ_LEN=8, DEPTH=2,
                     HEADS=2, DIM_HEAD=16,
                     ATTN_TYPES=["full", "axial_row"])


@pytest.fixture(scope="module")
def workdir(tmp_path_factory):
    return tmp_path_factory.mktemp("work")


@pytest.fixture(scope="module")
def trained_vae(tiny_dataset, workdir):
    os.environ["DALLE_TPU_HPARAMS"] = json.dumps(VAE_HPARAMS)
    cwd = os.getcwd()
    os.chdir(workdir)
    try:
        import train_vae

        train_vae.main(["--image_folder", str(tiny_dataset),
                        "--image_size", "16"])
    finally:
        os.chdir(cwd)
        del os.environ["DALLE_TPU_HPARAMS"]
    return workdir / "vae-final.pt"


def test_train_vae_cli(trained_vae, workdir):
    assert trained_vae.exists()
    assert (workdir / "vae.pt").exists()
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(trained_vae)
    assert set(ckpt) >= {"hparams", "weights"}
    assert ckpt["hparams"]["num_tokens"] == 32
    # recon sample grids were written
    assert any((workdir / "samples" / "vae").glob("*.png"))
    # step log with `epoch iter loss lr` lines exists
    logs = list(workdir.glob("dalle_tpu_train_vae-*.txt"))
    assert logs and len(logs[0].read_text().strip().split("\n")) >= 1


@pytest.fixture(scope="module")
def trained_dalle(trained_vae, tiny_dataset, tiny_tokenizer_json, workdir):
    os.environ["DALLE_TPU_HPARAMS"] = json.dumps(DALLE_HPARAMS)
    cwd = os.getcwd()
    os.chdir(workdir)
    try:
        import train_dalle

        train_dalle.main(["--vae_path", str(trained_vae),
                          "--image_text_folder", str(tiny_dataset),
                          "--bpe_path", str(tiny_tokenizer_json),
                          "--truncate_captions",
                          "--learning_rate", "1e-3",
                          "--epochs", "1"])
    finally:
        os.chdir(cwd)
        del os.environ["DALLE_TPU_HPARAMS"]
    return workdir / "dalle-final.pt"


def test_train_dalle_cli(trained_dalle, workdir):
    assert trained_dalle.exists()
    assert (workdir / "dalle.pt").exists()
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(trained_dalle)
    # the reference's checkpoint dict keys (train_dalle.py:178-183) plus our
    # resume-exactness extras (SURVEY.md §5.3 gap fix)
    assert set(ckpt) >= {"hparams", "vae_params", "weights", "opt_state",
                         "scheduler", "epoch"}
    # epoch-0 sweep checkpoint cadence (every 19th epoch incl. 0, ref :425)
    assert any((workdir / "sweep1").glob("*.pt"))
    # periodic sample generation
    assert any((workdir / "samples" / "dalle").glob("*.png"))
    logs = list(workdir.glob("dalle_tpu_train_transformer-*.txt"))
    assert logs
    line = logs[0].read_text().strip().split("\n")[0].split(" ")
    assert len(line) == 4  # epoch iter loss lr


def test_train_dalle_resume(trained_dalle, tiny_dataset, tiny_tokenizer_json,
                            workdir):
    # deliberately do NOT re-export the tiny model geometry: the resumed
    # checkpoint's hparams (text_seq_len=8, dim=32, ...) must win over the
    # script constants (text_seq_len=80, dim=256)
    os.environ["DALLE_TPU_HPARAMS"] = json.dumps({"BATCH_SIZE": 4})
    cwd = os.getcwd()
    os.chdir(workdir)
    try:
        import train_dalle

        # resume from the saved ckpt and train up to 2 epochs total
        train_dalle.main(["--dalle_path", str(trained_dalle),
                          "--image_text_folder", str(tiny_dataset),
                          "--bpe_path", str(tiny_tokenizer_json),
                          "--truncate_captions",
                          "--learning_rate", "1e-3",
                          "--epochs", "2"])
    finally:
        os.chdir(cwd)
        del os.environ["DALLE_TPU_HPARAMS"]
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(workdir / "dalle-final.pt")
    assert int(ckpt["epoch"]) == 2


def _run_train_dalle(workdir, hparams, extra_args, vae_path, dataset,
                     tokenizer_json):
    os.environ["DALLE_TPU_HPARAMS"] = json.dumps(hparams)
    cwd = os.getcwd()
    os.chdir(workdir)
    try:
        import train_dalle

        train_dalle.main(["--vae_path", str(vae_path),
                          "--image_text_folder", str(dataset),
                          "--bpe_path", str(tokenizer_json),
                          "--truncate_captions",
                          "--learning_rate", "1e-3",
                          "--epochs", "1"] + extra_args)
    finally:
        os.chdir(cwd)
        del os.environ["DALLE_TPU_HPARAMS"]


def _first_loss(workdir):
    logs = sorted(workdir.glob("dalle_tpu_train_transformer-*.txt"),
                  key=lambda p: p.stat().st_mtime)
    return float(logs[-1].read_text().strip().split("\n")[0].split(" ")[2])


@pytest.mark.slow
@pytest.mark.parametrize("sp_impl,sp", [("ring", 4), ("ulysses", 2)])
def test_train_dalle_sequence_parallel_cli(trained_vae, tiny_dataset,
                                           tiny_tokenizer_json,
                                           tmp_path_factory, sp_impl, sp):
    """`train_dalle.py --mesh_sp N` trains on the 8-CPU mesh and its
    first-step loss matches a dense run bit-for-bit-ish (the sp loss psums
    the identical phase CE; VERDICT round-1 item 3)."""
    wd_dense = tmp_path_factory.mktemp(f"sp_dense_{sp_impl}")
    wd_sp = tmp_path_factory.mktemp(f"sp_{sp_impl}")
    # seq_len = 8 text + 16 image = 24, divisible by sp 4 and 2.  The crop
    # rng is deterministic per (seed, idx, epoch), so the two runs see
    # bit-identical batches and the dense run is an exact reference.
    hp = dict(DALLE_HPARAMS, BATCH_SIZE=4, DEPTH=2)
    _run_train_dalle(wd_dense, hp, [], trained_vae, tiny_dataset,
                     tiny_tokenizer_json)
    _run_train_dalle(wd_sp, hp, ["--mesh_sp", str(sp), "--sp_impl", sp_impl],
                     trained_vae, tiny_dataset, tiny_tokenizer_json)
    assert (wd_sp / "dalle-final.pt").exists()
    # same data order (seeded shuffle), same init seed -> same first loss
    assert abs(_first_loss(wd_dense) - _first_loss(wd_sp)) < 2e-4
    # the sp checkpoint is topology-free: no plan fields in hparams
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    hparams = dict(load_checkpoint(wd_sp / "dalle-final.pt")["hparams"])
    assert "ring_axis" not in hparams and "sp_size" not in hparams


def test_train_dalle_pipeline_cli(trained_vae, tiny_dataset,
                                  tiny_tokenizer_json, tmp_path_factory):
    """`train_dalle.py --pipeline_stages 2` trains on the 8-CPU mesh; the
    saved checkpoint carries the standard dense param layout."""
    wd = tmp_path_factory.mktemp("pp_cli")
    hp = dict(DALLE_HPARAMS, BATCH_SIZE=8, DEPTH=4)
    _run_train_dalle(wd, hp, ["--pipeline_stages", "2",
                              "--pipeline_microbatches", "2"],
                     trained_vae, tiny_dataset, tiny_tokenizer_json)
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(wd / "dalle-final.pt")
    assert "layers_3_ff" in ckpt["weights"]["transformer"]  # dense layout
    assert "opt_state" not in ckpt  # weights-only in pp mode (documented)
    assert np.isfinite(_first_loss(wd))


@pytest.mark.slow
def test_train_dalle_fp16_cli(trained_vae, tiny_dataset, tiny_tokenizer_json,
                              tmp_path_factory):
    """`train_dalle.py --fp16` (the reference's mixed-precision flag,
    ref train_dalle.py:55; here it selects bf16 compute — no loss scaling
    needed on TPU) trains end-to-end: finite losses, loadable float32
    checkpoint (params are kept f32; only compute runs bf16)."""
    wd = tmp_path_factory.mktemp("fp16_cli")
    _run_train_dalle(wd, DALLE_HPARAMS, ["--fp16"], trained_vae,
                     tiny_dataset, tiny_tokenizer_json)
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(wd / "dalle-final.pt")
    assert np.isfinite(_first_loss(wd))
    kernel = ckpt["weights"]["transformer"]["layers_0_attn"]["attn"][
        "to_qkv"]["kernel"]
    assert np.asarray(kernel).dtype == np.float32  # params stay f32


@pytest.mark.parametrize("dispatch_args", [
    [],  # dense default
    # capacity dispatch stays covered in the fast tier by test_moe; the
    # CLI-flag plumbing sweep is nightly-only
    pytest.param(["--ff_expert_dispatch", "capacity",
                  "--ff_expert_capacity_factor", "2.0"],
                 marks=pytest.mark.slow),
])
def test_train_dalle_moe_cli(trained_vae, tiny_dataset, tiny_tokenizer_json,
                             tmp_path_factory, dispatch_args):
    """`train_dalle.py --ff_experts 2` trains routed-MoE feed-forwards in
    both dispatch modes; the expert count is a checkpointed model
    hyperparameter while the dispatch mode is per-run execution strategy
    (same params) and stays out of the checkpoint."""
    wd = tmp_path_factory.mktemp("moe_cli")
    hp = dict(DALLE_HPARAMS, BATCH_SIZE=4, DEPTH=2)
    _run_train_dalle(wd, hp,
                     ["--ff_experts", "2", "--ff_expert_top_k", "1"]
                     + dispatch_args,
                     trained_vae, tiny_dataset, tiny_tokenizer_json)
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(wd / "dalle-final.pt")
    assert ckpt["hparams"]["ff_experts"] == 2
    assert "ff_expert_dispatch" not in ckpt["hparams"]  # plan, not identity
    ff = ckpt["weights"]["transformer"]["layers_0_ff"]
    assert "moe" in ff and ff["moe"]["w_in"].shape[0] == 2
    assert np.isfinite(_first_loss(wd))


def test_train_then_generate_over_a_named_trunk(trained_vae, tiny_dataset,
                                                tiny_tokenizer_json,
                                                tmp_path_factory):
    """`train_dalle.py --trunk jamba-tiny` trains DALL-E over a Mamba +
    multi-query trunk; the block spec is a checkpointed model
    hyperparameter, so `generate.py` rebuilds the same model from the
    checkpoint alone and samples through the mixed decode carry."""
    wd = tmp_path_factory.mktemp("trunk_cli")
    _run_train_dalle(wd, dict(BATCH_SIZE=4, TEXT_SEQ_LEN=8),
                     ["--trunk", "jamba-tiny"], trained_vae, tiny_dataset,
                     tiny_tokenizer_json)
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(wd / "dalle-final.pt")
    assert ckpt["hparams"]["trunk"]["mixers"] == ["mamba", "attention",
                                                  "mamba"]
    assert ckpt["hparams"]["dim"] == 32
    layers = ckpt["weights"]["transformer"]
    assert "ssm" in layers["layers_0_ssm"] and "attn" in layers[
        "layers_1_attn"]
    assert "table" in ckpt["weights"] and "text_emb" not in ckpt["weights"]
    assert np.isfinite(_first_loss(wd))
    cwd = os.getcwd()
    os.chdir(wd)
    try:
        import generate

        generate.main(["--dalle_path", str(wd / "dalle-final.pt"),
                       "--text", "red bird", "--num_images", "2",
                       "--batch_size", "2",
                       "--bpe_path", str(tiny_tokenizer_json),
                       "--outputs_dir", str(wd / "outputs")])
    finally:
        os.chdir(cwd)
    images = list((wd / "outputs").rglob("*.jpg")) + list(
        (wd / "outputs").rglob("*.png"))
    assert len(images) == 2


def test_train_then_generate_over_a_linear_attention_trunk(
        trained_vae, tiny_dataset, tiny_tokenizer_json, tmp_path_factory):
    """`train_dalle.py --trunk olmo-hybrid-tiny` trains DALL-E over
    gated-delta-rule layers and a full-attention layer with the norm on each
    sublayer's output; the spec (its new fields too) rides in the
    checkpoint's hparams, so `generate.py` rebuilds the model from the
    checkpoint alone and samples through the matrix-state carry."""
    wd = tmp_path_factory.mktemp("linear_cli")
    _run_train_dalle(wd, dict(BATCH_SIZE=4, TEXT_SEQ_LEN=8),
                     ["--trunk", "olmo-hybrid-tiny"], trained_vae,
                     tiny_dataset, tiny_tokenizer_json)
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(wd / "dalle-final.pt")
    trunk = ckpt["hparams"]["trunk"]
    assert trunk["mixers"] == ["gdn", "gdn", "gdn", "attention"]
    assert (trunk["norm_at"], trunk["qk_norm"], trunk["lin_key_dim"],
            trunk["lin_value_dim"], trunk["tied_table"]) == (
        "output", True, 8, 16, False)
    layers = ckpt["weights"]["transformer"]
    assert "A_log" in layers["layers_0_gdn"]["gdn"]
    assert "q_norm" in layers["layers_3_attn"]["attn"]
    assert "layers_0_mixer_norm" in layers and "norm" not in layers[
        "layers_0_ff"]
    assert "head" in ckpt["weights"] and "text_pos_emb" in ckpt["weights"]
    assert np.isfinite(_first_loss(wd))
    cwd = os.getcwd()
    os.chdir(wd)
    try:
        import generate

        generate.main(["--dalle_path", str(wd / "dalle-final.pt"),
                       "--text", "red bird", "--num_images", "2",
                       "--batch_size", "2",
                       "--bpe_path", str(tiny_tokenizer_json),
                       "--outputs_dir", str(wd / "outputs")])
    finally:
        os.chdir(cwd)
    images = list((wd / "outputs").rglob("*.jpg")) + list(
        (wd / "outputs").rglob("*.png"))
    assert len(images) == 2


def test_train_generate_and_serve_a_routed_windowed_trunk(
        trained_vae, tiny_dataset, tiny_tokenizer_json, tmp_path_factory):
    """`train_dalle.py --trunk smallthinker-tiny` trains two steps of DALL-E
    over routed experts and ring-cached window layers; the spec rides in the
    checkpoint's hparams, so `generate.py` rebuilds the model from the
    checkpoint alone; and a `SlotArena` built from the same checkpoint
    serves it to the static path's logits."""
    import jax
    import jax.numpy as jnp

    wd = tmp_path_factory.mktemp("routed_cli")
    _run_train_dalle(wd, dict(BATCH_SIZE=6, TEXT_SEQ_LEN=8),
                     ["--trunk", "smallthinker-tiny"], trained_vae,
                     tiny_dataset, tiny_tokenizer_json)
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(wd / "dalle-final.pt")
    trunk = ckpt["hparams"]["trunk"]
    assert trunk["mixers"] == ["attention", "window", "window", "window"]
    assert (trunk["ff"], trunk["experts"], trunk["window"],
            trunk["tied_table"]) == ("moe_reglu", 8, 8, False)
    weights = ckpt["weights"]
    assert "head" in weights and "text_pos_emb" not in weights
    assert weights["transformer"]["layers_1_ff"]["moe"]["w_gate"].shape[0] == 8
    log = next(wd.glob("dalle_tpu_train_transformer-*.txt"))
    assert len(log.read_text().strip().splitlines()) == 2      # two steps
    assert np.isfinite(_first_loss(wd))
    cwd = os.getcwd()
    os.chdir(wd)
    try:
        import generate

        generate.main(["--dalle_path", str(wd / "dalle-final.pt"),
                       "--text", "red bird", "--num_images", "2",
                       "--batch_size", "2",
                       "--bpe_path", str(tiny_tokenizer_json),
                       "--outputs_dir", str(wd / "outputs")])
    finally:
        os.chdir(cwd)
    images = list((wd / "outputs").rglob("*.jpg")) + list(
        (wd / "outputs").rglob("*.png"))
    assert len(images) == 2

    from dalle_pytorch_tpu import DALLE
    from dalle_pytorch_tpu.cli import load_dalle_checkpoint
    from dalle_pytorch_tpu.serve.engine import SlotArena

    dalle, cfg, params = load_dalle_checkpoint(str(wd / "dalle-final.pt"))[:3]
    assert cfg.cache_lens == (24, 8, 8, 8)
    variables = {"params": params}
    arena = SlotArena(dalle, variables, 2, filter_thres=1.0)
    text = jnp.asarray([[3, 7, 0, 0, 0, 0, 0, 0]], jnp.int32)
    first, caches = arena.prefill(text)
    arena.admit(1, first, caches, jax.random.PRNGKey(0), 1.0, clock=3)
    want, _ = dalle.apply(variables, arena.state["code"][1:2], caches,
                          jnp.asarray(cfg.text_seq_len + 1),
                          method=DALLE.decode_step)
    got, _ = dalle.apply(variables, arena.state["code"],
                         arena.state["caches"], arena.state["index"], None,
                         jnp.int32(3), None, method=DALLE.decode_step)
    np.testing.assert_allclose(np.asarray(got[1], np.float32),
                               np.asarray(want[0], np.float32), rtol=2e-2,
                               atol=2e-2)


def test_generate_cli(trained_dalle, tiny_tokenizer_json, workdir):
    cwd = os.getcwd()
    os.chdir(workdir)
    try:
        import generate

        generate.main(["--dalle_path", str(trained_dalle),
                       "--text", "red bird",
                       "--num_images", "2",
                       "--batch_size", "2",
                       "--top_p", "0.9",
                       "--bpe_path", str(tiny_tokenizer_json),
                       "--outputs_dir", str(workdir / "outputs")])
    finally:
        os.chdir(cwd)
    out_dirs = list((workdir / "outputs").iterdir())
    assert out_dirs
    jpgs = list(out_dirs[0].glob("*.jpg"))
    assert len(jpgs) == 2


def test_generate_cli_pickle_eval_mode(trained_dalle, tiny_tokenizer_json,
                                       tmp_path):
    """Eval mode (no --text): generate for every caption of a pickled
    pandas DataFrame in big batches (ref generate.py:118-156)."""
    pd = pytest.importorskip("pandas")

    df = pd.DataFrame({
        "caption": ["red bird", "blue square", "green circle"],
        "fname": ["a.jpg", "b.jpg", "c.jpg"],
        "name": ["a", "b", "c"],
    })
    pkl = tmp_path / "caps.pkl"
    df.to_pickle(pkl)

    import generate

    # every path is absolute, so no cwd dance is needed in eval mode
    generate.main(["--dalle_path", str(trained_dalle),
                   "--captions_pickle", str(pkl),
                   "--batch_size", "2",
                   "--bpe_path", str(tiny_tokenizer_json),
                   "--outputs_dir", str(tmp_path / "eval_out")])
    jpgs = list((tmp_path / "eval_out").glob("*.jpg"))
    assert len(jpgs) == 3  # one image per caption


@pytest.mark.slow
def test_genrank_cli_with_clip_vit(trained_dalle, tiny_tokenizer_json,
                                   workdir):
    """Ranking through a converted-official-CLIP-style (CLIPViT) ranker."""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.clip_vit import CLIPViT, CLIPViTConfig
    from dalle_pytorch_tpu.utils.checkpoint import save_checkpoint

    cfg = CLIPViTConfig(image_size=16, patch_size=8, vision_width=32,
                        vision_layers=2, vision_heads=4, embed_dim=16,
                        text_width=32, text_layers=2, text_heads=4,
                        context_length=8, vocab_size=600)
    clip = CLIPViT(cfg)
    params = clip.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32),
                       jnp.zeros((1, 16, 16, 3)))["params"]
    save_checkpoint(workdir / "clip_vit.pt",
                    {"hparams": cfg.to_dict(), "weights": params})

    # tiny CLIP merges file (same format as tests/test_tokenizer.py)
    merges = ["#version: test", "r e", "re d", "b i", "bi rd"]
    (workdir / "clip_merges.txt").write_text("\n".join(merges) + "\n")

    cwd = os.getcwd()
    os.chdir(workdir)
    try:
        import genrank

        genrank.main(["--dalle_path", str(trained_dalle),
                      "--text", "red bird",
                      "--num_images", "4",
                      "--bpe_path", str(tiny_tokenizer_json),
                      "--clip_path", str(workdir / "clip_vit.pt"),
                      "--clip_bpe_path", str(workdir / "clip_merges.txt"),
                      "--out_path", str(workdir / "rank_vit_out")])
    finally:
        os.chdir(cwd)
    results = (workdir / "rank_vit_out" / "results.txt").read_text().strip()
    mname, mean, std = results.split(" ")
    # a real ranker produces non-degenerate logits
    assert float(std) >= 0.0 and mean not in ("nan", "0.0")
    # fused default: the CLIP-ranked run wrote no intermediate image files
    assert not list((workdir / "rank_vit_out").rglob("*.jpg"))


@pytest.mark.slow
def test_genrank_ranking_order_with_trained_clip(tiny_tokenizer_json,
                                                 tmp_path, monkeypatch):
    """genrank's ranking math must be discriminative, not just run: a tiny
    CLIP trained in-test to separate 'red' from 'blue' solid images, driven
    through the FULL CLI (save -> JPEG re-read -> preprocess -> rank ->
    results.txt), must score every caption-matching image above every
    mismatched one (VERDICT r2 weak #7; ref harness genrank.py:68-77,
    :128-135).  Generation is stubbed with constructed images — ranking
    can't be asserted against a sampler's randomness; the generate path has
    its own tests."""
    import jax
    import jax.numpy as jnp

    import genrank
    from dalle_pytorch_tpu.data.tokenizer import HugTokenizer
    from dalle_pytorch_tpu.models.clip import CLIP, CLIPConfig
    from dalle_pytorch_tpu.training import (make_clip_train_step,
                                            make_optimizer)
    from dalle_pytorch_tpu.utils.checkpoint import save_checkpoint

    cfg = CLIPConfig(
        dim_text=32, dim_image=32, dim_latent=16, num_text_tokens=64,
        text_enc_depth=1, text_seq_len=8, text_heads=2, num_visual_tokens=64,
        visual_enc_depth=1, visual_heads=2, visual_image_size=16,
        visual_patch_size=8)
    tok = HugTokenizer(tiny_tokenizer_json)
    captions = tok.tokenize(["red", "blue"], cfg.text_seq_len)
    solid = np.zeros((2, 16, 16, 3), np.float32)
    solid[0, ..., 0] = 0.9  # red
    solid[1, ..., 2] = 0.9  # blue

    def preprocessed(images01):
        """The exact normalization genrank applies before scoring."""
        return (images01 - genrank._CLIP_MEAN) / genrank._CLIP_STD

    model = CLIP(cfg)
    rng = np.random.default_rng(0)
    text = jnp.asarray(captions, jnp.int32)
    params = model.init(jax.random.PRNGKey(1), text,
                        jnp.asarray(preprocessed(solid)))["params"]
    tx = make_optimizer(3e-3)
    opt_state = jax.jit(tx.init)(params)
    step = make_clip_train_step(model, tx, donate=False)
    for _ in range(60):
        noisy = solid + rng.normal(0, 0.03, solid.shape).astype(np.float32)
        params, opt_state, loss = step(
            params, opt_state, text, jnp.asarray(preprocessed(noisy)), None)
    assert float(loss) < np.log(2) * 0.5, "tiny CLIP failed to separate"

    clip_path = tmp_path / "clip_trained.pt"
    save_checkpoint(clip_path, {"hparams": cfg.to_dict(),
                                "weights": jax.device_get(params)})

    # 3 caption-matching (red) + 3 mismatched (blue) candidates, shuffled
    # order [red, blue, red, blue, red, blue]
    cand = np.zeros((6, 32, 32, 3), np.float32)
    for i in range(6):
        base = solid[i % 2]
        cand[i] = np.clip(
            np.repeat(np.repeat(base, 2, 0), 2, 1)
            + rng.normal(0, 0.03, (32, 32, 3)), 0, 1)

    monkeypatch.setattr(
        genrank, "generate_images",
        lambda *a, **k: (cand, HugTokenizer(tiny_tokenizer_json)))

    out = tmp_path / "rank_out"
    # --save_all: this test drives the legacy file-based path (its stub
    # seam is generate_images; the fused default's scorer equivalence is
    # pinned in tests/test_generation_equiv.py)
    genrank.main(["--dalle_path", "dalle-fake.pt", "--text", "red",
                  "--num_images", "6", "--bpe_path",
                  str(tiny_tokenizer_json), "--clip_path", str(clip_path),
                  "--out_path", str(out), "--save_all"])

    logits = np.load(out / "Bdalle-fake.npy")
    red_scores, blue_scores = logits[0::2], logits[1::2]
    # every matching image outranks every mismatched one
    assert red_scores.min() > blue_scores.max(), logits
    line = (out / "results.txt").read_text().strip().split(" ")
    assert len(line) == 3 and np.isfinite(float(line[1]))


def test_genrank_cli(trained_dalle, tiny_tokenizer_json, workdir):
    """Default genrank = the fused on-device pipeline: full outputs
    (results.txt, logits .npy, ranking grid) with ZERO intermediate image
    files on disk — the JPEG round-trip is gone."""
    cwd = os.getcwd()
    os.chdir(workdir)
    try:
        import genrank

        genrank.main(["--dalle_path", str(trained_dalle),
                      "--text", "blue square",
                      "--num_images", "4",
                      "--bpe_path", str(tiny_tokenizer_json),
                      "--out_path", str(workdir / "rank_out")])
    finally:
        os.chdir(cwd)
    rank_out = workdir / "rank_out"
    assert (rank_out / "results.txt").exists()
    line = (rank_out / "results.txt").read_text().strip().split(" ")
    assert len(line) == 3  # mname mean std
    assert list(rank_out.glob("B*.npy")) and list(rank_out.glob("B*.png"))
    # zero intermediate image files: no per-candidate JPEGs, no per-model
    # subfolder — the only image artifact is the final ranking grid
    assert not list(rank_out.rglob("*.jpg"))
    assert not [p for p in rank_out.iterdir() if p.is_dir()]


def test_genrank_cli_save_all_keeps_file_artifacts(trained_dalle,
                                                   tiny_tokenizer_json,
                                                   workdir):
    """--save_all preserves the reference's artifact behavior: every
    candidate saved as a JPEG in the per-model folder and ranked from the
    re-read files."""
    cwd = os.getcwd()
    os.chdir(workdir)
    try:
        import genrank

        genrank.main(["--dalle_path", str(trained_dalle),
                      "--text", "blue square",
                      "--num_images", "4",
                      "--bpe_path", str(tiny_tokenizer_json),
                      "--out_path", str(workdir / "rank_all_out"),
                      "--save_all"])
    finally:
        os.chdir(cwd)
    rank_out = workdir / "rank_all_out"
    assert (rank_out / "results.txt").exists()
    jpgs = list(rank_out.rglob("*.jpg"))
    assert len(jpgs) == 4  # one per candidate, in the per-model subfolder


def test_legacy_ckpt_resume_with_flat_opt_state(trained_dalle, tiny_dataset,
                                                tiny_tokenizer_json, workdir,
                                                tmp_path):
    """Resume from a pre-DenseGeneral checkpoint: both the params AND the
    saved adam moments carry flat [d, 3*h*dh] to_qkv kernels; resume must
    reshape both to the current [d, 3, h, dh] layout and train."""
    import numpy as np

    from dalle_pytorch_tpu.utils.checkpoint import (load_checkpoint,
                                                    save_checkpoint)

    def flatten_qkv(tree):
        if isinstance(tree, list):
            # opt_state is saved as a flat LIST of leaves (train_dalle
            # save_model); qkv-shaped moments are the [d, 3, h, dh] arrays
            for i, val in enumerate(tree):
                if np.ndim(val) == 4 and np.shape(val)[1] == 3:
                    v = np.asarray(val)
                    tree[i] = v.reshape(v.shape[0], -1)
            return
        if not isinstance(tree, dict):
            return
        for key, val in tree.items():
            if key == "to_qkv" and isinstance(val, dict) and \
                    np.ndim(val.get("kernel")) == 4:
                k = np.asarray(val["kernel"])
                val["kernel"] = k.reshape(k.shape[0], -1)
            else:
                flatten_qkv(val)

    ckpt = load_checkpoint(trained_dalle)
    flatten_qkv(ckpt["weights"])
    flatten_qkv(ckpt["opt_state"])
    assert any(np.ndim(v) == 2 for v in ckpt["opt_state"]
               if hasattr(v, "shape")), "no adam moments were flattened"
    legacy_path = tmp_path / "legacy.pt"
    save_checkpoint(legacy_path, ckpt)

    os.environ["DALLE_TPU_HPARAMS"] = json.dumps({"BATCH_SIZE": 4})
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        import train_dalle

        train_dalle.main(["--dalle_path", str(legacy_path),
                          "--image_text_folder", str(tiny_dataset),
                          "--bpe_path", str(tiny_tokenizer_json),
                          "--truncate_captions",
                          "--epochs", str(int(ckpt["epoch"]) + 1)])
    finally:
        os.chdir(cwd)
        del os.environ["DALLE_TPU_HPARAMS"]
    out = load_checkpoint(tmp_path / "dalle-final.pt")
    k = None

    def find_qkv(tree):
        nonlocal k
        if not isinstance(tree, dict):
            return
        for key, val in tree.items():
            if key == "to_qkv" and isinstance(val, dict):
                k = np.asarray(val["kernel"])
            else:
                find_qkv(val)

    find_qkv(out["weights"])
    assert k is not None and k.ndim == 4  # re-saved in the current layout


def test_legacy_qkv_checkpoint_migration():
    """Pre-DenseGeneral checkpoints (flat [d, 3*h*dh] to_qkv kernels) load
    via migrate_qkv_kernels (bit-compatible reshape)."""
    import numpy as np

    from dalle_pytorch_tpu.utils.checkpoint import migrate_qkv_kernels

    d, h, dh = 8, 2, 4
    legacy = {
        "transformer": {
            "layers_0_attn": {"attn": {"to_qkv": {
                "kernel": np.arange(d * 3 * h * dh, dtype=np.float32)
                .reshape(d, 3 * h * dh)}}},
        },
        "other": {"kernel": np.ones((d, d), np.float32)},
    }
    out = migrate_qkv_kernels(legacy, dim_head=dh)
    k = out["transformer"]["layers_0_attn"]["attn"]["to_qkv"]["kernel"]
    assert k.shape == (d, 3, h, dh)
    # bit-compatible: flattening restores the original layout
    np.testing.assert_array_equal(
        k.reshape(d, -1),
        np.arange(d * 3 * h * dh, dtype=np.float32).reshape(d, 3 * h * dh))
    # non-qkv kernels untouched
    assert out["other"]["kernel"].shape == (d, d)
    # idempotent on current-format checkpoints
    again = migrate_qkv_kernels(out, dim_head=dh)
    assert again["transformer"]["layers_0_attn"]["attn"]["to_qkv"][
        "kernel"].shape == (d, 3, h, dh)


def test_legacy_joint_head_checkpoint_migration():
    """Pre-split checkpoints (single joint-vocab to_logits_dense kernel)
    load via migrate_head_kernels: an exact column partition at
    total_text_tokens, applied through dicts AND the list nesting of
    serialized optimizer states (Adam moments)."""
    import numpy as np

    from dalle_pytorch_tpu.utils.checkpoint import migrate_head_kernels

    d, v_text, v_img = 8, 5, 3
    total = v_text + v_img
    kern = np.arange(d * total, dtype=np.float32).reshape(d, total)
    bias = np.arange(total, dtype=np.float32)
    legacy = {"to_logits_dense": {"kernel": kern.copy(), "bias": bias.copy()},
              "other": {"kernel": np.ones((d, d), np.float32)}}
    out = migrate_head_kernels(legacy, v_text)
    head = out["to_logits_dense"]
    assert set(head) == {"text_kernel", "image_kernel",
                         "text_bias", "image_bias"}
    np.testing.assert_array_equal(head["text_kernel"], kern[:, :v_text])
    np.testing.assert_array_equal(head["image_kernel"], kern[:, v_text:])
    np.testing.assert_array_equal(head["text_bias"], bias[:v_text])
    np.testing.assert_array_equal(head["image_bias"], bias[v_text:])
    assert out["other"]["kernel"].shape == (d, d)
    # idempotent on current-format checkpoints
    again = migrate_head_kernels(out, v_text)
    assert set(again["to_logits_dense"]) == set(head)

    # optimizer states nest the param tree inside lists (optax chain):
    opt_like = [{"mu": {"to_logits_dense": {"kernel": kern.copy(),
                                            "bias": bias.copy()}}},
                {"count": np.zeros(())}]
    migrate_head_kernels(opt_like, v_text)
    assert set(opt_like[0]["mu"]["to_logits_dense"]) == set(head)


def test_analyze_logs_cli(tmp_path, capsys):
    """Per-epoch mean/std summary + CSV from `epoch iter loss lr` logs
    (script equivalent of the reference's analysis notebook)."""
    log = tmp_path / "run-a.txt"
    rows = []
    for e in range(2):
        for i in range(5):
            rows.append(f"{e} {i} {4.0 - e - 0.1 * i} 0.001")
    log.write_text("\n".join(rows) + "\n")

    import analyze_logs

    csv = tmp_path / "summary.csv"
    analyze_logs.main([str(log), "--csv", str(csv)])
    out = capsys.readouterr().out
    assert "run-a" in out and "10 steps" in out and "2 epochs" in out
    lines = csv.read_text().strip().split("\n")
    assert len(lines) == 3  # header + 2 epochs
    assert lines[0].split(",")[:2] == ["run", "epoch"]


@pytest.mark.slow
def test_train_dalle_sharded_checkpoints(trained_vae, tiny_dataset,
                                         tiny_tokenizer_json, tmp_path):
    """--sharded_checkpoints writes Orbax dirs ({name}.orbax, per-host
    shard IO) and resume accepts the directory transparently."""
    os.environ["DALLE_TPU_HPARAMS"] = json.dumps(DALLE_HPARAMS)
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        import train_dalle

        train_dalle.main(["--vae_path", str(trained_vae),
                          "--image_text_folder", str(tiny_dataset),
                          "--bpe_path", str(tiny_tokenizer_json),
                          "--truncate_captions", "--epochs", "1",
                          "--sharded_checkpoints"])
        final = tmp_path / "dalle-final.pt.orbax"
        assert final.is_dir()

        # resume from the Orbax directory
        train_dalle.main(["--dalle_path", str(final),
                          "--image_text_folder", str(tiny_dataset),
                          "--bpe_path", str(tiny_tokenizer_json),
                          "--truncate_captions", "--epochs", "2",
                          "--sharded_checkpoints"])
    finally:
        os.chdir(cwd)
        del os.environ["DALLE_TPU_HPARAMS"]
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(tmp_path / "dalle-final.pt.orbax")
    assert int(ckpt["epoch"]) == 2


def test_train_dalle_preemption(trained_vae, tiny_dataset, tiny_tokenizer_json,
                                tmp_path, monkeypatch):
    """SIGTERM mid-training (preemption notice) checkpoints and stops
    cleanly: ./dalle.pt is written, no final artifact, heartbeat files
    exist, and the checkpoint resumes (SURVEY.md §5.3 — the reference just
    dies)."""
    import signal

    from dalle_pytorch_tpu.utils.failure import Heartbeat
    from dalle_pytorch_tpu.utils.logging import TrainLogger

    calls = {"n": 0}
    orig_step = TrainLogger.step

    def step_then_preempt(self, *a, **k):
        orig_step(self, *a, **k)
        calls["n"] += 1
        if calls["n"] == 2:  # deliver the signal a couple of steps in
            signal.raise_signal(signal.SIGTERM)

    monkeypatch.setattr(TrainLogger, "step", step_then_preempt)
    monkeypatch.setenv("DALLE_TPU_HPARAMS", json.dumps(DALLE_HPARAMS))
    monkeypatch.chdir(tmp_path)
    import train_dalle

    # would run 50 tiny epochs if the stop flag were ignored
    train_dalle.main(["--vae_path", str(trained_vae),
                      "--image_text_folder", str(tiny_dataset),
                      "--bpe_path", str(tiny_tokenizer_json),
                      "--truncate_captions", "--epochs", "50",
                      "--heartbeat_dir", "hb"])
    assert calls["n"] < 20, "training ignored the shutdown request"
    assert (tmp_path / "dalle.pt").exists()
    assert not (tmp_path / "dalle-final.pt").exists()
    hb = Heartbeat.read(tmp_path / "hb" / "heartbeat-p0.json")
    assert hb["step"] >= 1 and hb["process"] == 0

    # the interrupt checkpoint is a valid resume point
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(tmp_path / "dalle.pt")
    assert set(ckpt) >= {"hparams", "vae_params", "weights", "opt_state",
                         "scheduler", "epoch"}
    monkeypatch.setattr(TrainLogger, "step", orig_step)
    train_dalle.main(["--dalle_path", str(tmp_path / "dalle.pt"),
                      "--image_text_folder", str(tiny_dataset),
                      "--bpe_path", str(tiny_tokenizer_json),
                      "--truncate_captions", "--epochs", "1",
                      "--learning_rate", "1e-3"])
    assert (tmp_path / "dalle-final.pt").exists()


def test_train_vae_resume(trained_vae, tiny_dataset, workdir, monkeypatch):
    """--resume_path continues a VAE run exactly (optimizer, lr, temperature,
    epoch) — capability the reference lacks entirely (SURVEY.md §5.3)."""
    monkeypatch.setenv("DALLE_TPU_HPARAMS", json.dumps(dict(VAE_HPARAMS,
                                                            EPOCHS=2)))
    monkeypatch.chdir(workdir)
    import train_vae

    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    before = load_checkpoint(workdir / "vae-final.pt")
    assert {"opt_state", "epoch", "temperature", "lr"} <= set(before)

    train_vae.main(["--image_folder", str(tiny_dataset), "--image_size", "16",
                    "--resume_path", str(workdir / "vae-final.pt")])
    after = load_checkpoint(workdir / "vae-final.pt")
    assert int(after["epoch"]) == 2
    # resumed from the checkpoint's epoch (1), not from scratch
    assert float(after["lr"]) <= float(before["lr"])


@pytest.mark.slow
def test_sharded_checkpoint_cross_mesh_resume(trained_vae, tiny_dataset,
                                              tiny_tokenizer_json, tmp_path,
                                              monkeypatch):
    """Elastic resume across topologies: a run checkpointed under the
    default dp-only mesh resumes under dp2 x fsdp2 x tp2 (and vice versa
    would too) — mesh shape is a per-run choice, not baked into the
    checkpoint."""
    monkeypatch.setenv("DALLE_TPU_HPARAMS", json.dumps(DALLE_HPARAMS))
    monkeypatch.chdir(tmp_path)
    import train_dalle

    train_dalle.main(["--vae_path", str(trained_vae),
                      "--image_text_folder", str(tiny_dataset),
                      "--bpe_path", str(tiny_tokenizer_json),
                      "--truncate_captions", "--epochs", "1",
                      "--sharded_checkpoints"])
    final = tmp_path / "dalle-final.pt.orbax"
    assert final.is_dir()

    train_dalle.main(["--dalle_path", str(final),
                      "--image_text_folder", str(tiny_dataset),
                      "--bpe_path", str(tiny_tokenizer_json),
                      "--truncate_captions", "--epochs", "2",
                      "--sharded_checkpoints",
                      "--mesh_fsdp", "2", "--mesh_tp", "2"])
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(final)
    assert int(ckpt["epoch"]) == 2


@pytest.mark.slow
def test_sharded_resume_from_legacy_joint_head(trained_vae, tiny_dataset,
                                               tiny_tokenizer_json, tmp_path,
                                               monkeypatch):
    """An Orbax checkpoint written before the per-phase head split (joint
    to_logits_dense/{kernel,bias}) must still resume: weights migrate via
    the replicated restore+split path, optimizer state restarts fresh with
    a notice (legacy moment lists no longer align leaf-for-leaf)."""
    import numpy as np

    monkeypatch.setenv("DALLE_TPU_HPARAMS", json.dumps(DALLE_HPARAMS))
    monkeypatch.chdir(tmp_path)
    import train_dalle
    from dalle_pytorch_tpu.utils.checkpoint import (load_checkpoint_sharded,
                                                    save_checkpoint_sharded)

    train_dalle.main(["--vae_path", str(trained_vae),
                      "--image_text_folder", str(tiny_dataset),
                      "--bpe_path", str(tiny_tokenizer_json),
                      "--truncate_captions", "--epochs", "1",
                      "--sharded_checkpoints"])
    final = tmp_path / "dalle-final.pt.orbax"
    ckpt = load_checkpoint_sharded(final)
    head = ckpt["weights"]["to_logits_dense"]
    # rebuild the pre-split layout: joint kernel/bias column-concat
    joint = {
        "kernel": np.concatenate([np.asarray(head["text_kernel"]),
                                  np.asarray(head["image_kernel"])], axis=1),
        "bias": np.concatenate([np.asarray(head["text_bias"]),
                                np.asarray(head["image_bias"])])}
    ckpt["weights"]["to_logits_dense"] = joint
    legacy = tmp_path / "legacy.pt.orbax"
    save_checkpoint_sharded(legacy, ckpt)

    train_dalle.main(["--dalle_path", str(legacy),
                      "--image_text_folder", str(tiny_dataset),
                      "--bpe_path", str(tiny_tokenizer_json),
                      "--truncate_captions", "--epochs", "2",
                      "--sharded_checkpoints"])
    resumed = load_checkpoint_sharded(tmp_path / "dalle-final.pt.orbax")
    new_head = resumed["weights"]["to_logits_dense"]
    assert set(new_head) == {"text_kernel", "image_kernel",
                             "text_bias", "image_bias"}
    # the split is the exact column partition of the legacy joint kernel
    np.testing.assert_array_equal(
        np.asarray(new_head["text_kernel"]).shape[1]
        + np.asarray(new_head["image_kernel"]).shape[1],
        joint["kernel"].shape[1])
    assert int(resumed["epoch"]) == 2


def test_train_vae_sharded_checkpoints_and_resume(tiny_dataset, tmp_path,
                                                  monkeypatch):
    """train_vae --sharded_checkpoints writes Orbax dirs and --resume_path
    accepts them (multi-host symmetric with train_dalle)."""
    monkeypatch.setenv("DALLE_TPU_HPARAMS", json.dumps(VAE_HPARAMS))
    monkeypatch.chdir(tmp_path)
    import train_vae

    train_vae.main(["--image_folder", str(tiny_dataset), "--image_size", "16",
                    "--sharded_checkpoints"])
    final = tmp_path / "vae-final.pt.orbax"
    assert final.is_dir()
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(final)
    assert int(ckpt["epoch"]) == 1 and ckpt["hparams"]["num_tokens"] == 32

    monkeypatch.setenv("DALLE_TPU_HPARAMS", json.dumps(dict(VAE_HPARAMS,
                                                            EPOCHS=2)))
    train_vae.main(["--image_folder", str(tiny_dataset), "--image_size", "16",
                    "--resume_path", str(final), "--sharded_checkpoints"])
    assert int(load_checkpoint(final)["epoch"]) == 2


def test_train_then_generate_over_a_latent_attention_trunk(
        trained_vae, tiny_dataset, tiny_tokenizer_json, tmp_path_factory):
    """`train_dalle.py --trunk glm-flash-tiny` trains two steps of DALL-E
    over latent attention, a leading dense layer and sigmoid-routed experts
    with a shared one (2 of 8 held); the spec rides in the checkpoint's
    hparams, so `generate.py` rebuilds the model from the checkpoint alone
    and samples through the latent cache."""
    wd = tmp_path_factory.mktemp("latent_cli")
    _run_train_dalle(wd, dict(BATCH_SIZE=6, TEXT_SEQ_LEN=8),
                     ["--trunk", "glm-flash-tiny"], trained_vae,
                     tiny_dataset, tiny_tokenizer_json)
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    ckpt = load_checkpoint(wd / "dalle-final.pt")
    trunk = ckpt["hparams"]["trunk"]
    assert (trunk["mixers"], trunk["ff"], trunk["dense_layers"],
            trunk["experts"], trunk["experts_held"], trunk["experts_first"],
            trunk["kv_rank"]) == (["mla"], "moe_swiglu_shared", 1, 8, 2, 2,
                                  20)
    weights = ckpt["weights"]["transformer"]
    assert "gate" in weights["layers_0_ff"]               # the dense layer
    assert weights["layers_1_ff"]["moe"]["w_gate"].shape[0] == 2
    assert weights["layers_1_attn"]["mla"]["w_kvb"].shape == (20, 4, 22)
    assert np.isfinite(_first_loss(wd))
    cwd = os.getcwd()
    os.chdir(wd)
    try:
        import generate

        generate.main(["--dalle_path", str(wd / "dalle-final.pt"),
                       "--text", "red bird", "--num_images", "2",
                       "--batch_size", "2",
                       "--bpe_path", str(tiny_tokenizer_json),
                       "--outputs_dir", str(wd / "outputs")])
    finally:
        os.chdir(cwd)
    images = list((wd / "outputs").rglob("*.jpg")) + list(
        (wd / "outputs").rglob("*.png"))
    assert len(images) == 2
