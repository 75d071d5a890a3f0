"""Scaled geometry presets — the ROADMAP's model-scale ladder.

One place naming the (config geometry, ParallelPlan) pairs a run can ask
for by name, so ``train_dalle.py``'s hard-coded CUB block is one preset
of many and the analysis suite can gate rungs that do not fit a single
chip.  Four rungs of the 2021 block and six other trunks today:

==========  ======  ========  =======================================
preset      params  geometry  role
==========  ======  ========  =======================================
tiny        ~0.04M  dim-32    tests / smoke (chip-free twins)
cub         ~15M    dim-256   the production CUB-200 run (PR 1..14)
cub-512     ~345M   dim-512   first scale rung where HBM genuinely
                              binds: S4 says ~13.2 GiB/device under
                              fsdp-4 vs v5e-4's 14.4 GiB budget
cub-1024    ~1.3B   dim-1024  the MFU rung (ROADMAP direction 1):
                              4096 image tokens (fmap-64), the first
                              geometry where arithmetic intensity
                              crosses the v5e ridge and fsdp-x-tp /
                              dcn-hybrid plan choices diverge —
                              graftplan's autotuner sweep lives here
jamba-tiny  ~0.05M  dim-32    a ``TrunkSpec`` trunk (Mamba + one
                              attention layer) at toy width (tests)
jamba2-3b   3.03B   dim-2560  DALL-E over AI21-Jamba2-3B's trunk: 26
                              Mamba-1 + 2 multi-query attention
                              layers, bf16; one chip generates
smallthinker-tiny  (~0.1M, dim-32)  routed ReGLU experts, one global and
                              three rotated sliding-window layers, an
                              untied head, at toy width (tests)
smallthinker-21ba3b  (2.37B, dim-2560)  DALL-E over the first period (4
                              of 52 layers) of SmallThinker-21BA3B's
                              trunk, all 64 experts of each, bf16; one
                              chip generates
glm-flash-tiny  (~0.1M, dim-32)  latent attention, a leading dense
                              layer, sigmoid-routed SwiGLU experts with a
                              shared expert, 2 of 8 held, at toy width
                              (tests)
glm-4.7-flash  (1.15B, dim-2048)  DALL-E over one chip's share of
                              GLM-4.7-Flash's trunk (5 of 47 layers, 8 of
                              64 experts a layer held: eight chips share
                              each layer), bf16; one chip generates 128
                              candidates of n = 4,352 over a 3.2 GB
                              latent cache
laguna-tiny  (~0.1M, dim-32)  rotated global and sliding-window layers
                              of different head counts, YaRN, a head
                              gate, softmax-routed SwiGLU experts with a
                              shared expert, 2 of 8 held (tests)
laguna-s-2.1  (1.65B, dim-3072)  DALL-E over one chip's share of
                              Laguna-S-2.1's trunk (5 of 48 layers, 16 of
                              256 experts a layer held: sixteen chips
                              share each layer), bf16; one chip
                              generates 96 candidates of n = 4,352
==========  ======  ========  =======================================

``cub-512`` and ``cub-1024`` are ALSO :data:`~dalle_pytorch_tpu.parallel.
plan.PLAN_REGISTRY` entries (fsdp-4, and the fsdp-4 x tp-2 hybrid
respectively — the ZeRO/tensor shardings that make those counts fit at
all): registry name and config preset resolve together via
:data:`SCALE_PRESETS`.  Scale-preset registry entries are excluded from
``tools/spmd_check.py``'s default per-push matrix (their S4 compile at
opt0 takes ~8 minutes at dim-512) — ``spmd_check --presets`` runs the
full S4 HBM proof, and the nightly CI job carries it; contract_check
covers the cheap half (geometry instantiates, param count in band,
shardings lower) on every push, and ``tools/graftmem.py`` commits the
rung's walker-only memory timeline to the perf ledger.

Config factories import jax lazily: ``tools/spmd_check.py`` must set its
platform env BEFORE anything touches jax, and it imports this module.
"""
from __future__ import annotations

import functools

#: Param-count acceptance bands (min, max) per preset — contract_check's
#: cheap chip-free gate that a geometry edit doesn't silently change the
#: rung's scale class.
PARAM_BANDS = {
    "tiny": (0.01e6, 1e6),
    "cub": (10e6, 25e6),
    "cub-512": (300e6, 400e6),
    "cub-1024": (1.15e9, 1.45e9),
    "jamba-tiny": (0.01e6, 1e6),
    "jamba2-3b": (2.9e9, 3.2e9),
    "smallthinker-tiny": (0.01e6, 1e6),
    "smallthinker-21ba3b": (2.3e9, 2.45e9),
    "olmo-hybrid-tiny": (0.01e6, 1e6),
    "olmo-hybrid-7b": (2.4e9, 2.47e9),
    "glm-flash-tiny": (0.01e6, 1e6),
    "glm-4.7-flash": (1.1e9, 1.2e9),
    "laguna-tiny": (0.01e6, 1e6),
    "laguna-s-2.1": (1.6e9, 1.7e9),
    "nemotron-tiny": (0.01e6, 1e6),
    "nemotron-3-nano-30b-a3b": (1.55e9, 1.65e9),
}


def tiny_config(**overrides):
    """Small geometry: seq 24 (divisible by sp=2), heads 4 (divisible by
    the ulysses sp axis), depth 2 (divisible by pp=2)."""
    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=32, depth=2, heads=4, dim_head=8, num_text_tokens=50,
                text_seq_len=8, num_image_tokens=32, image_size=64,
                image_fmap_size=4)
    base.update(overrides)
    return DALLEConfig(**base)


def cub200_config():
    """The CUB-200 model as the reference trains it (ref train_dalle.py:
    74-97): the four-pattern attention cycle, 8192 image tokens, bf16."""
    import jax.numpy as jnp

    from dalle_pytorch_tpu import DALLEConfig

    return DALLEConfig(
        dim=256, num_text_tokens=7800, text_seq_len=80, depth=8, heads=8,
        dim_head=64, attn_types=("full", "axial_row", "axial_col", "conv_like"),
        num_image_tokens=8192, image_size=256, image_fmap_size=32,
        dtype=jnp.bfloat16,
    )


def cub_config(**overrides):
    """The production CUB-200 geometry (:func:`cub200_config`'s widths,
    all-``full`` attention, 1024 image tokens) at the checkpoint-eval
    dtype (f32 activations)."""
    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=256, depth=8, heads=8, dim_head=64,
                num_text_tokens=7800, text_seq_len=80,
                num_image_tokens=1024, image_size=256, image_fmap_size=32)
    base.update(overrides)
    return DALLEConfig(**base)


def cub512_config(**overrides):
    """The dim-512 scale rung (~345M params): same CUB data geometry
    (80-token captions, 32x32 code grid), transformer widened to dim-512
    and deepened to 80 layers — the first rung where the S4 budget
    genuinely binds (fsdp-4: ~13.2 GiB/device live vs v5e-4's
    0.9 x 16 GiB) rather than fitting everywhere trivially."""
    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=512, depth=80, heads=8, dim_head=64,
                num_text_tokens=7800, text_seq_len=80,
                num_image_tokens=1024, image_size=256, image_fmap_size=32)
    base.update(overrides)
    return DALLEConfig(**base)


def cub1024_config(**overrides):
    """The dim-1024 MFU rung (~1.3B params): captions unchanged but the
    code grid doubled to 64x64 (4096 image tokens — a finer VAE stride at
    the same 256px crops), dim-1024 x 76 layers x 16 heads.  This is the
    first geometry where the roofline's arithmetic intensity crosses the
    v5e ridge (~240 FLOP/byte) and plan choice genuinely matters: pure
    fsdp no longer fits the S4 budget at batch 8, the fsdp-4 x tp-2
    hybrid does, and on multi-slice topologies the dcn placement of the
    grad all-reduce decides whether the step is ICI- or DCN-bound
    (tools/plan_search.py sweeps exactly those choices).

    ``use_remat`` is ON at this rung: without per-block rematerialization
    the backward pass keeps every block's activations live and the
    compiled S4 estimate shows ~216 GiB/device of XLA temporaries at
    batch 8 — no chip holds that.  Remat trades the recompute (the
    roofline is byte-bound here anyway) for per-layer-bounded liveness:
    the jaxpr walker's peak drops 2541 -> 86 GiB global (~10.7
    GiB/device under the hybrid plan).  Note the *opt0 compiled*
    estimate still reads ~132 GiB/device — opt0 buffer assignment does
    not reuse buffers across remat regions, so it sums all 76 blocks —
    which is why spmd_check.S4_PRESET_EXPECT declares this rung "over"
    and gates the compiled proof as a drift sentinel rather than a fit
    proof (the walker + P3 own the fit verdict here)."""
    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=1024, depth=76, heads=16, dim_head=64,
                num_text_tokens=7800, text_seq_len=80,
                num_image_tokens=1024, image_size=256, image_fmap_size=64,
                use_remat=True)
    base.update(overrides)
    return DALLEConfig(**base)


#: AI21-Jamba2-3B's trunk (huggingface.co/ai21labs/AI21-Jamba2-3B,
#: config.json): layer i of 28 is multi-query attention iff i mod 14 == 7,
#: else Mamba-1; every width as published.
JAMBA2_3B_TRUNK = dict(
    mixers=("mamba",) * 7 + ("attention",) + ("mamba",) * 6, ff_dim=8192,
    kv_heads=1, norm="rms", norm_eps=1e-6, ff="swiglu", ssm_expand=2,
    ssm_state=16, ssm_conv=4, ssm_dt_rank=160, param_dtype="bfloat16")


def jamba_tiny_config(**overrides):
    """One attention layer among Mamba layers at toy width (tests)."""
    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=32, depth=3, heads=4, dim_head=8, num_text_tokens=50,
                text_seq_len=8, num_image_tokens=32, image_size=64,
                image_fmap_size=4,
                trunk=dict(mixers=("mamba", "attention", "mamba"), ff_dim=96,
                           ssm_state=4, ssm_dt_rank=4,
                           param_dtype="float32"))
    base.update(overrides)
    return DALLEConfig(**base)


def jamba2_3b_config(**overrides):
    """DALL-E's client over the AI21-Jamba2-3B trunk (3.03B parameters,
    6.06 GB in bfloat16: generation fits one v5e chip whole): the tied
    table's 65,536 rows are 57,088 text ids + 256 per-position pad ids +
    8,192 image codes of a 256 px, 32 x 32 code grid.
    ``benchmark/configs/jamba2-3b.json`` is the same model as the benchmark
    runs it."""
    import jax.numpy as jnp

    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=2560, depth=28, heads=20, dim_head=128,
                num_text_tokens=57088, text_seq_len=256,
                num_image_tokens=8192, image_size=256, image_fmap_size=32,
                attn_types=("full",), trunk=JAMBA2_3B_TRUNK,
                dtype=jnp.bfloat16)
    base.update(overrides)
    return DALLEConfig(**base)


#: SmallThinker-21BA3B-Instruct's trunk (huggingface.co/PowerInfer/
#: SmallThinker-21BA3B-Instruct, config.json): layer i of 52 is global
#: attention without rotation iff i mod 4 == 0, else rotated (theta 1.5e6)
#: and bounded to 4,096 keys; 28 queries over 4 keys of 128; 64 ReGLU experts
#: of 768, 6 a token, the router on the layer's input; an untied head.
SMALLTHINKER_21BA3B_TRUNK = dict(
    mixers=("attention", "window", "window", "window"), kv_heads=4,
    norm="rms", norm_eps=1e-6, ff="moe_reglu", window=4096,
    rope_theta=1500000.0, experts=64, experts_per_token=6, expert_dim=768,
    tied_table=False, param_dtype="bfloat16")


def smallthinker_tiny_config(**overrides):
    """The same period at toy width, its window (8) shorter than its prompt
    (9 positions) and its sequence (24) (tests)."""
    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=32, depth=4, heads=4, dim_head=8, num_text_tokens=50,
                text_seq_len=8, num_image_tokens=32, image_size=64,
                image_fmap_size=4,
                trunk=dict(SMALLTHINKER_21BA3B_TRUNK, kv_heads=2, window=8,
                           experts=8, experts_per_token=3, expert_dim=24,
                           param_dtype="float32"))
    base.update(overrides)
    return DALLEConfig(**base)


def smallthinker_21ba3b_config(**overrides):
    """DALL-E's client over one whole period (4 of 52 layers: global,
    window, window, window) of the SmallThinker-21BA3B-Instruct trunk, every
    width as published and all 64 experts of each layer: 2.37B parameters,
    4.75 GB in bfloat16.  The 151,936 rows of the embedding and of the
    separate head are 143,488 text ids + 256 per-position pad ids + 8,192
    image codes of a 512 px, 64 x 64 code grid (n = 4,352, so that the
    4,096 window cuts keys).  ``benchmark/configs/smallthinker-21ba3b.json``
    is the same model as the benchmark runs it; the other 48 layers would
    lie on further chips."""
    import jax.numpy as jnp

    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=2560, depth=4, heads=28, dim_head=128,
                num_text_tokens=143488, text_seq_len=256,
                num_image_tokens=8192, image_size=512, image_fmap_size=64,
                attn_types=("full",), trunk=SMALLTHINKER_21BA3B_TRUNK,
                dtype=jnp.bfloat16)
    base.update(overrides)
    return DALLEConfig(**base)


#: Olmo-Hybrid-7B's trunk (huggingface.co/allenai/Olmo-Hybrid-7B,
#: config.json): layer i of 32 is full attention iff i mod 4 == 3 (30 heads
#: of 128 over 30 keys, no rotation: ``rope_theta`` null), else
#: gated-delta-rule linear attention (30 heads, a 96 x 192 float32 state a
#: head behind 4-tap convolutions); SwiGLU 11008; the norm on each
#: sublayer's output, queries and keys normed; an untied head.
OLMO_HYBRID_7B_TRUNK = dict(
    mixers=("gdn", "gdn", "gdn", "attention"), ff_dim=11008, kv_heads=30,
    norm="rms", norm_eps=1e-6, ff="swiglu", norm_at="output", qk_norm=True,
    lin_key_dim=96, lin_value_dim=192, lin_conv=4, tied_table=False,
    param_dtype="bfloat16")


def olmo_hybrid_tiny_config(**overrides):
    """The same period at toy width (tests)."""
    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=64, depth=4, heads=4, dim_head=16, num_text_tokens=50,
                text_seq_len=8, num_image_tokens=32, image_size=64,
                image_fmap_size=4,
                trunk=dict(OLMO_HYBRID_7B_TRUNK, ff_dim=96, kv_heads=4,
                           lin_key_dim=8, lin_value_dim=16,
                           param_dtype="float32"))
    base.update(overrides)
    return DALLEConfig(**base)


def olmo_hybrid_7b_config(**overrides):
    """DALL-E's client over two whole periods (8 of 32 layers: linear,
    linear, linear, full, twice) of the Olmo-Hybrid-7B trunk, every width
    as published: 2.44B parameters, 4.87 GB in bfloat16.  The 100,352 rows
    of the embedding and of the separate head are 91,904 text ids + 256
    per-position pad ids + 8,192 image codes of a 256 px, 32 x 32 code grid
    (n = 1280); the trunk has no position encoding, so the client's learned
    position embeddings are added before it.
    ``benchmark/configs/olmo-hybrid-7b.json`` is the same model as the
    benchmark runs it; the other 24 layers would lie on further chips."""
    import jax.numpy as jnp

    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=3840, depth=8, heads=30, dim_head=128,
                num_text_tokens=91904, text_seq_len=256,
                num_image_tokens=8192, image_size=256, image_fmap_size=32,
                attn_types=("full",), trunk=OLMO_HYBRID_7B_TRUNK,
                dtype=jnp.bfloat16)
    base.update(overrides)
    return DALLEConfig(**base)


#: GLM-4.7-Flash's trunk (huggingface.co/zai-org/GLM-4.7-Flash, config.json,
#: ``glm4_moe_lite``): 47 layers of multi-head latent attention (20 heads;
#: queries through a normed 768 bottleneck; one normed 512 latent and one
#: shared 64-wide rotary key a position; 192 + 64 query/key and 256 value
#: dimensions a head; theta 1e6); layer 0 a dense SwiGLU of 10,240, the rest
#: 64 sigmoid-routed SwiGLU experts of 1,536, 4 a token, weights renormalised
#: and scaled by 1.8, beside one shared expert; an untied head.  HERE: one
#: chip's share of a deployment in which eight chips share each layer, the
#: 64 experts split 8 a chip (``experts_held``, experts 0-7).
GLM_4_7_FLASH_TRUNK = dict(
    mixers=("mla",), ff_dim=10240, norm="rms", norm_eps=1e-5,
    ff="moe_swiglu_shared", scoring="sigmoid", rope_theta=1000000.0,
    q_rank=768, kv_rank=512,
    nope_dim=192, rope_dim=64, value_dim=256, dense_layers=1, experts=64,
    experts_per_token=4, expert_dim=1536, experts_held=8, experts_first=0,
    shared_experts=1, route_scale=1.8, tied_table=False,
    param_dtype="bfloat16")


def glm_flash_tiny_config(**overrides):
    """The same trunk at toy width with every mechanism (tests): one dense
    and two routed layers, 8 experts of which 2 are held, 2 a token, the
    ranks and the parts of a head all of different sizes."""
    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=32, depth=3, heads=4, dim_head=8, num_text_tokens=50,
                text_seq_len=8, num_image_tokens=32, image_size=64,
                image_fmap_size=4,
                trunk=dict(GLM_4_7_FLASH_TRUNK, ff_dim=80, q_rank=24,
                           kv_rank=20, nope_dim=12, rope_dim=4, value_dim=10,
                           experts=8, experts_per_token=2, expert_dim=24,
                           experts_held=2, experts_first=2,
                           param_dtype="float32"))
    base.update(overrides)
    return DALLEConfig(**base)


def glm_4_7_flash_config(**overrides):
    """DALL-E's client over one chip's share of GLM-4.7-Flash's trunk, every
    width as published: the leading dense layer and four of the 46 routed
    layers that follow (5 of 47), each routed layer's router over all 64
    experts and the banks of experts 0-7 (8 of 64: eight chips share each
    layer), the shared expert, the whole 154,880-row table and head: 1.146B
    parameters, 2.29 GB in bfloat16.  The rows are 146,432 text ids + 256
    per-position pad ids + 8,192 image codes of a 512 px, 64 x 64 code grid
    (n = 4,352).  ``dim_head`` is the 256 query/key dimensions of a head
    (192 unrotated + 64 rotated).  ``benchmark/configs/glm-4.7-flash.json``
    is the same model as the benchmark runs it; the next-token-prediction
    layer is not held."""
    import jax.numpy as jnp

    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=2048, depth=5, heads=20, dim_head=256,
                num_text_tokens=146432, text_seq_len=256,
                num_image_tokens=8192, image_size=512, image_fmap_size=64,
                attn_types=("full",), trunk=GLM_4_7_FLASH_TRUNK,
                dtype=jnp.bfloat16)
    base.update(overrides)
    return DALLEConfig(**base)


#: Laguna-S-2.1's trunk (huggingface.co/poolside/Laguna-S-2.1, config.json,
#: ``laguna``): layer i of 48 is global iff i mod 4 == 0, 48 query heads
#: rotated by YaRN (theta 5e5, the leading 64 of 128 dimensions, factor 128
#: over 8,192 positions, attention factor 1.4852), else bounded to 512 keys,
#: 72 query heads rotated over all 128 dimensions (theta 1e4); 8 key heads
#: of 128 in both; a sigmoid gate a head on every attention layer; layer 0
#: a dense SwiGLU of 12,288, the rest 256 softmax-routed SwiGLU experts of
#: 1,024, 10 a token, weights renormalised and scaled by 2.5, beside one
#: shared expert; an untied head.  HERE: one chip's share of a deployment
#: in which sixteen chips share each layer, the 256 experts split 16 a chip
#: (``experts_held``, experts 0-15).
LAGUNA_S_2_1_TRUNK = dict(
    mixers=("rotated", "window", "window", "window"), ff_dim=12288,
    kv_heads=8, norm="rms", norm_eps=1e-6, ff="moe_swiglu_shared",
    scoring="softmax", window=512, rope_theta=10000.0, window_heads=72,
    global_rope_theta=500000.0, global_rope_fraction=0.5, yarn_factor=128.0,
    yarn_original_len=8192, head_gate=True,
    dense_layers=1, experts=256, experts_per_token=10, expert_dim=1024,
    experts_held=16, experts_first=0, shared_experts=1, route_scale=2.5,
    tied_table=False, param_dtype="bfloat16")


def laguna_tiny_config(**overrides):
    """The same trunk at toy width with every mechanism (tests): a dense
    global layer, three window layers and a routed global one; global and
    window layers of different head counts (4 and 6 over 2 keys of 16), the
    leading 8 dimensions of a global head rotated by YaRN (its ramp over
    pairs 1-3), a window of 4 that the 24 positions wrap five times, 8
    experts of which 2 are held, 3 a token."""
    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=32, depth=5, heads=4, dim_head=16, num_text_tokens=50,
                text_seq_len=8, num_image_tokens=32, image_size=64,
                image_fmap_size=4,
                trunk=dict(LAGUNA_S_2_1_TRUNK, ff_dim=80, kv_heads=2,
                           window=4, window_heads=6, experts=8,
                           experts_per_token=3, expert_dim=24,
                           experts_held=2, experts_first=2,
                           param_dtype="float32"))
    base.update(overrides)
    return DALLEConfig(**base)


def laguna_s_2_1_config(**overrides):
    """DALL-E's client over one chip's share of Laguna-S-2.1's trunk, every
    width as published: the leading dense layer and the four routed layers
    that follow (5 of 48: global, window, window, window, global), each
    routed layer's router over all 256 experts and the banks of experts
    0-15 (16 of 256: sixteen chips share each layer), the shared expert, the
    whole 100,352-row table and head: 1.653B parameters, 3.31 GB in
    bfloat16.  The rows are 91,904 text ids + 256 per-position pad ids +
    8,192 image codes of a 512 px, 64 x 64 code grid (n = 4,352, so that
    the 512-key window wraps its ring 4.5 times a request).
    ``benchmark/configs/laguna-s-2.1.json`` is the same model as the
    benchmark runs it; the other 43 layers would lie on further chips."""
    import jax.numpy as jnp

    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=3072, depth=5, heads=48, dim_head=128,
                num_text_tokens=91904, text_seq_len=256,
                num_image_tokens=8192, image_size=512, image_fmap_size=64,
                attn_types=("full",), trunk=LAGUNA_S_2_1_TRUNK,
                dtype=jnp.bfloat16)
    base.update(overrides)
    return DALLEConfig(**base)


#: NVIDIA-Nemotron-3-Nano-30B-A3B's trunk (huggingface.co/nvidia/
#: NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, config.json, ``nemotron_h``): every
#: layer is ONE sublayer, as ``hybrid_override_pattern`` says: a Mamba-2
#: mixer ("M": 64 heads of 64, a 64 x 128 float32 state a head, B and C in 8
#: groups, a 4-tap convolution, chunks of 128), 128 sigmoid-routed relu^2
#: experts of 1,856, 6 a token, weights renormalised and scaled by 2.5,
#: beside a shared relu^2 expert of 3,712 ("E"), or unrotated grouped
#: attention, 32 queries over 2 keys of 128 ("*"); an untied head.  HERE:
#: layers 0-8, ``MEMEM*EME`` (one period of the published 52), and one
#: chip's share of a deployment in which eight chips share each layer, the
#: 128 experts split 16 a chip (``experts_held``, experts 0-15).
NEMOTRON_3_NANO_30B_A3B_TRUNK = dict(
    mixers=("mamba2", "none", "mamba2", "none", "mamba2", "attention",
            "none", "mamba2", "none"),
    sublayers=1, kv_heads=2, norm="rms", norm_eps=1e-5,
    ssm_state=128, ssm_conv=4, ssd_heads=64, ssd_head_dim=64, ssd_groups=8,
    ssd_chunk=128, ff="moe_swiglu_shared", expert_act="relu2",
    scoring="sigmoid", experts=128, experts_per_token=6, expert_dim=1856,
    experts_held=16, experts_first=0, shared_experts=1, shared_dim=3712,
    route_scale=2.5, tied_table=False, param_dtype="bfloat16")


def nemotron_tiny_config(**overrides):
    """The same trunk at toy width with every mechanism (tests): the
    published period ``MEMEM*EME``, 4 Mamba-2 heads of 8 channels in 2
    groups with a state of 8 and chunks of 8 (so that a prompt of 9
    positions ends inside a chunk), 8 experts of which 4 are held, 3 a
    token, a shared expert of its own width."""
    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=32, depth=9, heads=4, dim_head=8, num_text_tokens=50,
                text_seq_len=8, num_image_tokens=32, image_size=64,
                image_fmap_size=4,
                trunk=dict(NEMOTRON_3_NANO_30B_A3B_TRUNK, ssm_state=8,
                           ssd_heads=4, ssd_head_dim=8, ssd_groups=2,
                           ssd_chunk=8, experts=8, experts_per_token=3,
                           expert_dim=16, experts_held=4, experts_first=2,
                           shared_dim=24, param_dtype="float32"))
    base.update(overrides)
    return DALLEConfig(**base)


def nemotron_3_nano_30b_a3b_config(**overrides):
    """DALL-E's client over one chip's share of the Nemotron-3-Nano-30B-A3B
    trunk, every width as published: layers 0-8 (``MEMEM*EME``: four
    Mamba-2 layers, four expert layers, one attention layer), each expert
    layer's router over all 128 experts and the banks of experts 0-15 (16 of
    128: eight chips share each layer), the shared expert, the whole
    131,072-row table and head: 1.603B parameters, 3.21 GB in bfloat16.  The
    rows are 122,624 text ids + 256 per-position pad ids + 8,192 image codes
    of a 256 px, 32 x 32 code grid (n = 1,280).
    ``benchmark/configs/nemotron-3-nano-30b-a3b.json`` is the same model as
    the benchmark runs it; the other 43 layers would lie on further
    chips."""
    import jax.numpy as jnp

    from dalle_pytorch_tpu import DALLEConfig

    base = dict(dim=2688, depth=9, heads=32, dim_head=128,
                num_text_tokens=122624, text_seq_len=256,
                num_image_tokens=8192, image_size=256, image_fmap_size=32,
                attn_types=("full",), trunk=NEMOTRON_3_NANO_30B_A3B_TRUNK,
                dtype=jnp.bfloat16)
    base.update(overrides)
    return DALLEConfig(**base)


#: Every named config geometry (CLI ``--preset`` surface).
CONFIG_PRESETS = {
    "tiny": tiny_config,
    "cub": cub_config,
    "cub-512": cub512_config,
    "cub-1024": cub1024_config,
    "jamba-tiny": jamba_tiny_config,
    "jamba2-3b": jamba2_3b_config,
    "smallthinker-tiny": smallthinker_tiny_config,
    "smallthinker-21ba3b": smallthinker_21ba3b_config,
    "olmo-hybrid-tiny": olmo_hybrid_tiny_config,
    "olmo-hybrid-7b": olmo_hybrid_7b_config,
    "glm-flash-tiny": glm_flash_tiny_config,
    "glm-4.7-flash": glm_4_7_flash_config,
    "laguna-tiny": laguna_tiny_config,
    "laguna-s-2.1": laguna_s_2_1_config,
    "nemotron-tiny": nemotron_tiny_config,
    "nemotron-3-nano-30b-a3b": nemotron_3_nano_30b_a3b_config,
}

#: The scale rungs that are ALSO plan-registry entries: registry name ->
#: config factory.  tools/spmd_check.py excludes these names from its
#: default per-push matrix and proves them under ``--presets``.
SCALE_PRESETS = {
    "cub-512": cub512_config,
    "cub-1024": cub1024_config,
}


def preset_config(name: str, **overrides):
    """Resolve a preset name to its config (ValueError on unknown)."""
    if name not in CONFIG_PRESETS:
        raise ValueError(f"unknown preset {name!r}; known: "
                         f"{sorted(CONFIG_PRESETS)}")
    return CONFIG_PRESETS[name](**overrides)


@functools.lru_cache(maxsize=None)
def preset_param_count(name: str) -> int:
    """Chip-free param count of a preset's DALLE (eval_shape — nothing
    executes).  Pure per name (presets take no free parameters), so the
    eval_shape trace — seconds at dim-1024 — runs once per process even
    when several gates band-check the same rung."""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu import DALLE

    cfg = preset_config(name)
    dalle = DALLE(cfg)
    text = jax.ShapeDtypeStruct((1, cfg.text_seq_len), jnp.int32)
    codes = jax.ShapeDtypeStruct((1, cfg.image_seq_len), jnp.int32)
    params = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text,
                            codes)["params"]
    return sum(int(leaf.size) for leaf in jax.tree.leaves(params))


def check_param_band(name: str) -> str:
    """contract_check's preset gate: the param count sits inside the
    rung's declared band.  Returns the PASS detail; raises ValueError."""
    lo, hi = PARAM_BANDS[name]
    n = preset_param_count(name)
    if not lo <= n <= hi:
        raise ValueError(
            f"preset {name!r}: {n / 1e6:.1f}M params outside the declared "
            f"band [{lo / 1e6:.0f}M, {hi / 1e6:.0f}M] — a geometry edit "
            "changed the rung's scale class; update presets.PARAM_BANDS "
            "deliberately if intended")
    return f"{n / 1e6:.1f}M params in band [{lo / 1e6:.0f}M, {hi / 1e6:.0f}M]"
