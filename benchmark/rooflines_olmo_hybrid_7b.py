"""Operations and bytes a decode tick of the ``olmo-hybrid-7b`` configuration
needs, computed from shapes: the yardstick's own arithmetic for the two
rooflines that configuration brings.  They count the work the mathematics
needs, whatever implements it: a linear-attention layer's state read once and
written once a tick (an update that reads it twice, or carries it padded,
reads under 100).

Every function takes a ``DALLEConfig``-like object with a ``trunk`` (``dim``,
``depth``, ``heads``, ``dim_head``, ``text_seq_len``, ``image_fmap_size``,
``num_image_tokens``; ``trunk.mixers``, ``ff_dim``, ``kv_heads``,
``lin_key_dim``, ``lin_value_dim``, ``lin_conv``).  Matrices, convolution
taps, the table and the head are bfloat16 (2 bytes), ``A_log``, ``dt_bias``
and the gains float32; the linear-attention state is float32, the convolution
window and the key/value cache bfloat16.
"""
from __future__ import annotations

MATRIX_BYTES = 2
STATE_BYTES = 4
WINDOW_BYTES = 2
CACHE_BYTES = 2


def _mixers(cfg) -> list:
    m = cfg.trunk.mixers
    return [m[i % len(m)] for i in range(cfg.depth)]


def _channels(cfg) -> int:
    """Channels of a linear layer's one convolution: q, k and v."""
    t = cfg.trunk
    return cfg.heads * (2 * t.lin_key_dim + t.lin_value_dim)


def _state_elements(cfg) -> int:
    t = cfg.trunk
    return cfg.heads * t.lin_key_dim * t.lin_value_dim


def gdn_step_bytes(cfg, rows: float) -> float:
    """Bytes one tick's delta-rule updates must move, over all linear
    layers: each layer's float32 state and its convolution window read and
    written once for ``rows`` rows; the convolution's taps read once in
    bfloat16, ``A_log``, ``dt_bias`` and the output norm's gain in float32."""
    t = cfg.trunk
    state = 2 * rows * _state_elements(cfg) * STATE_BYTES
    window = 2 * rows * (t.lin_conv - 1) * _channels(cfg) * WINDOW_BYTES
    small = (t.lin_conv * _channels(cfg) * MATRIX_BYTES
             + (2 * cfg.heads + t.lin_value_dim) * 4)
    return float(_mixers(cfg).count("gdn") * (state + window + small))


def gdn_step_flops(cfg, rows: float) -> float:
    """FLOPs of the same: about 7 per state element (the decay's product,
    the two read-outs' multiply-adds, the write's multiply-add) and the
    convolution's taps."""
    t = cfg.trunk
    per_row = 7 * _state_elements(cfg) + 2 * t.lin_conv * _channels(cfg)
    return float(_mixers(cfg).count("gdn") * per_row * rows)


def decode_weight_params(cfg) -> dict:
    """Parameters one tick must read, as ``{"matrix": n, "f32": n}``: every
    layer's projections and SwiGLU, the linear layers' taps and small
    tensors, the norm gains, and the head's image rows (the embedding
    gathers ``rows`` rows of the table)."""
    t, dim, h = cfg.trunk, cfg.dim, cfg.heads
    inner = h * cfg.dim_head
    mlp = 3 * dim * t.ff_dim
    linear = (dim * h * (2 * t.lin_key_dim + 2 * t.lin_value_dim)
              + h * t.lin_value_dim * dim + 2 * dim * h
              + t.lin_conv * _channels(cfg))
    linear_f32 = 2 * h + t.lin_value_dim
    attn = dim * inner + dim * 2 * t.kv_heads * cfg.dim_head + inner * dim
    attn_f32 = inner + t.kv_heads * cfg.dim_head        # the q and k norms
    matrix = f32 = 0
    for kind in _mixers(cfg):
        matrix += mlp + (linear if kind == "gdn" else attn)
        f32 += 2 * dim + (linear_f32 if kind == "gdn" else attn_f32)
    return {"matrix": float(matrix + cfg.num_image_tokens * dim),
            "f32": float(f32 + dim)}


def decode_weight_bytes(cfg) -> float:
    p = decode_weight_params(cfg)
    return p["matrix"] * MATRIX_BYTES + p["f32"] * 4


def decode_kv_bytes(cfg, rows: float) -> float:
    """Bytes of keys and values one tick must read for ``rows`` rows,
    averaged over a request's ticks: a full causal layer reaches every
    position up to the one it decodes (``text_seq_len + 1`` prompt positions
    and the image positions so far), k and v, ``kv_heads`` heads."""
    n_pre = cfg.text_seq_len + 1
    ticks = cfg.image_fmap_size ** 2 - 1
    reachable = n_pre + 1 + (ticks - 1) / 2.0      # mean of n_pre + 1 + j
    per_layer = reachable * 2 * cfg.trunk.kv_heads * cfg.dim_head * CACHE_BYTES
    return float(_mixers(cfg).count("attention") * per_layer * rows)


def _least(nbytes: float, flops: float, peaks: dict) -> dict:
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_flops = flops / peaks["bf16_flops"]
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "flops",
            "bytes": nbytes, "flops": flops}


def gdn_step_least_s(cfg, rows: float, peaks: dict) -> dict:
    """Least time of one tick's delta-rule updates (convolution step, norms,
    decay, the state's update and read-out) over ``rows`` rows."""
    return _least(gdn_step_bytes(cfg, rows), gdn_step_flops(cfg, rows), peaks)


def tick_least_s(cfg, rows: float, peaks: dict) -> dict:
    """Least time of one whole decode tick over ``rows`` rows: weights, the
    head's image rows, the linear layers' state traffic and the attention
    layers' reachable keys and values over the memory bandwidth, or the
    tick's FLOPs (2 per matrix weight and row, plus the delta rule's and the
    attention's) over the matrix peak if that is longer."""
    kv = decode_kv_bytes(cfg, rows)
    nbytes = decode_weight_bytes(cfg) + gdn_step_bytes(cfg, rows) + kv
    taps = _mixers(cfg).count("gdn") * cfg.trunk.lin_conv * _channels(cfg)
    flops = (2.0 * (decode_weight_params(cfg)["matrix"] - taps) * rows
             + gdn_step_flops(cfg, rows)
             + kv / CACHE_BYTES * 2 * cfg.heads / cfg.trunk.kv_heads)
    return _least(nbytes, flops, peaks)
