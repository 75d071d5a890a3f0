"""Operations and bytes a decode tick of the ``jamba2-3b`` configuration
needs, computed from shapes: the yardstick's own arithmetic for the two
rooflines that configuration brings.

Every function takes a ``DALLEConfig``-like object with a ``trunk`` (``dim``,
``depth``, ``heads``, ``dim_head``, ``text_seq_len``, ``image_fmap_size``,
``num_image_tokens``; ``trunk.mixers``, ``ff_dim``, ``kv_heads``,
``ssm_expand``, ``ssm_state``, ``ssm_conv``, ``ssm_dt_rank``).  Matrices and
the table are bfloat16 (2 bytes), ``A_log``, ``D``, ``b_dt`` and the gains
float32; the recurrent state is float32, the convolution window and the
key/value cache bfloat16.
"""
from __future__ import annotations

MATRIX_BYTES = 2
STATE_BYTES = 4
WINDOW_BYTES = 2
CACHE_BYTES = 2


def _mixers(cfg) -> list:
    m = cfg.trunk.mixers
    return [m[i % len(m)] for i in range(cfg.depth)]


def _d_in(cfg) -> int:
    return cfg.trunk.ssm_expand * cfg.dim


def ssm_step_bytes(cfg, rows: float) -> float:
    """Bytes one tick's recurrent updates must move, over all Mamba layers:
    each layer's float32 state and its convolution window read and written
    once for ``rows`` rows; ``W_x``, ``W_dt``, the convolution's taps and
    bias read once in bfloat16, ``A_log``, ``D`` and ``b_dt`` in float32."""
    t, d_in = cfg.trunk, _d_in(cfg)
    state = 2 * rows * d_in * t.ssm_state * STATE_BYTES
    window = 2 * rows * d_in * (t.ssm_conv - 1) * WINDOW_BYTES
    weights = ((d_in * (t.ssm_dt_rank + 2 * t.ssm_state)
                + t.ssm_dt_rank * d_in + (t.ssm_conv + 1) * d_in)
               * MATRIX_BYTES + d_in * (t.ssm_state + 2) * 4)
    return float(_mixers(cfg).count("mamba") * (state + window + weights))


def ssm_step_flops(cfg, rows: float) -> float:
    """FLOPs of the same: the two small projections (2 per weight and row),
    the convolution's taps, and about 9 per state element (decay's product
    and exponential, the input's two products, the update's multiply-add,
    the read-out's multiply-add)."""
    t, d_in = cfg.trunk, _d_in(cfg)
    per_row = (2 * d_in * (t.ssm_dt_rank + 2 * t.ssm_state)
               + 2 * t.ssm_dt_rank * d_in + 2 * t.ssm_conv * d_in
               + 9 * d_in * t.ssm_state)
    return float(_mixers(cfg).count("mamba") * per_row * rows)


def decode_weight_params(cfg) -> dict:
    """Parameters one tick must read, as ``{"matrix": n, "f32": n}``: every
    layer's projections and SwiGLU, the Mamba layers' small tensors, the norm
    gains, and the image rows of the tied table (the head; the embedding
    gathers ``rows`` rows of the same)."""
    t, dim, d_in = cfg.trunk, cfg.dim, _d_in(cfg)
    inner = cfg.heads * cfg.dim_head
    mlp = 3 * dim * t.ff_dim
    mamba = (dim * 2 * d_in + d_in * dim
             + d_in * (t.ssm_dt_rank + 2 * t.ssm_state)
             + t.ssm_dt_rank * d_in + (t.ssm_conv + 1) * d_in)
    mamba_f32 = d_in * (t.ssm_state + 2) + t.ssm_dt_rank + 2 * t.ssm_state
    attn = dim * inner + dim * 2 * t.kv_heads * cfg.dim_head + inner * dim
    matrix = f32 = 0
    for kind in _mixers(cfg):
        matrix += mlp + (mamba if kind == "mamba" else attn)
        f32 += 2 * dim + (mamba_f32 if kind == "mamba" else 0)
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


def ssm_step_least_s(cfg, rows: float, peaks: dict) -> dict:
    """Least time of one tick's recurrent updates (convolution step, the two
    small projections, the state update and read-out) over ``rows`` rows."""
    return _least(ssm_step_bytes(cfg, rows), ssm_step_flops(cfg, rows), peaks)


def hybrid_tick_least_s(cfg, rows: float, peaks: dict) -> dict:
    """Least time of one whole decode tick over ``rows`` rows: weights, the
    table's image rows, the recurrent state's traffic and the attention
    layers' reachable keys and values over the memory bandwidth, or the
    tick's FLOPs (2 per matrix weight and row, plus the recurrence's and the
    attention's) over the matrix peak if that is longer."""
    nbytes = (decode_weight_bytes(cfg) + ssm_step_bytes(cfg, rows)
              + decode_kv_bytes(cfg, rows))
    t = cfg.trunk
    small = _mixers(cfg).count("mamba") * _d_in(cfg) * (
        t.ssm_dt_rank + 2 * t.ssm_state + t.ssm_dt_rank)
    flops = (2.0 * (decode_weight_params(cfg)["matrix"] - small) * rows
             + ssm_step_flops(cfg, rows)
             + decode_kv_bytes(cfg, rows) / CACHE_BYTES * 2
             * cfg.heads / t.kv_heads)
    return _least(nbytes, flops, peaks)
