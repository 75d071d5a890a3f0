"""Operations and bytes a decode tick of the ``nemotron-3-nano-30b-a3b``
configuration needs, computed from shapes: the yardstick's own arithmetic for
the two rooflines that configuration brings.  They count the work the
mathematics needs, whatever implements it: a Mamba-2 layer's float32 state
read once and written once a tick (an update that reads it twice, or carries
it padded, reads under 100).

Every function takes a ``DALLEConfig``-like object with a ``trunk`` (``dim``,
``depth``, ``heads``, ``dim_head``, ``text_seq_len``, ``image_fmap_size``,
``num_image_tokens``; ``trunk.mixers``, ``kv_heads``, ``ssd_heads``,
``ssd_head_dim``, ``ssd_groups``, ``ssm_state``, ``ssm_conv``, ``experts``,
``experts_per_token``, ``expert_dim``, ``experts_held``, ``shared_dim``).
Every layer is one sublayer: ``"mamba2"`` a Mamba-2 mixer, ``"none"`` the
expert feed-forward, ``"attention"`` grouped attention.  Matrices, the
convolution's taps and bias, the head and the key/value cache are bfloat16
(2 bytes); ``A_log``, ``D``, ``dt_bias``, the gains and the selection bias
float32; the Mamba-2 state float32 and its convolution window bfloat16.
"""
from __future__ import annotations

MATRIX_BYTES = 2
STATE_BYTES = 4
WINDOW_BYTES = 2
ACT_BYTES = 2
CACHE_BYTES = 2
F32_BYTES = 4


def _mixers(cfg) -> list:
    m = cfg.trunk.mixers
    return [m[i % len(m)] for i in range(cfg.depth)]


def _d_in(cfg) -> int:
    return cfg.trunk.ssd_heads * cfg.trunk.ssd_head_dim


def _conv_dim(cfg) -> int:
    """Channels of a Mamba-2 layer's convolution: x, B and C."""
    t = cfg.trunk
    return _d_in(cfg) + 2 * t.ssd_groups * t.ssm_state


def _state_elements(cfg) -> int:
    t = cfg.trunk
    return t.ssd_heads * t.ssd_head_dim * t.ssm_state


def ssd_step_bytes(cfg, rows: float) -> float:
    """Bytes one tick's Mamba-2 state updates must move, over all Mamba-2
    layers: each layer's float32 state read and written once for ``rows``
    rows; the update's inputs (``x``, ``B``, ``C``, ``dt`` and the gate
    ``z``, bfloat16) read and its output (bfloat16) written once a row;
    ``A_log``, ``D``, ``dt_bias`` and the norm's gain read once in float32.
    The convolution and its window are not the update's (scope
    ``ssd-conv``)."""
    t, d_in = cfg.trunk, _d_in(cfg)
    state = 2 * rows * _state_elements(cfg) * STATE_BYTES
    per_row = (_conv_dim(cfg) + t.ssd_heads + 2 * d_in) * ACT_BYTES
    small = (3 * t.ssd_heads + d_in) * F32_BYTES
    return float(_mixers(cfg).count("mamba2") * (state + rows * per_row
                                                 + small))


def ssd_step_flops(cfg, rows: float) -> float:
    """FLOPs of the same: about 5 per state element (the decay's product,
    the input's multiply-add, the read-out's multiply-add)."""
    return float(_mixers(cfg).count("mamba2") * 5 * _state_elements(cfg)
                 * rows)


def window_bytes(cfg, rows: float) -> float:
    """Bytes of the Mamba-2 layers' convolution windows, read and written
    once a tick for ``rows`` rows."""
    t = cfg.trunk
    return float(_mixers(cfg).count("mamba2") * 2 * rows * (t.ssm_conv - 1)
                 * _conv_dim(cfg) * WINDOW_BYTES)


def layer_params(cfg) -> dict:
    """Parameters of one layer of each kind, as ``{kind: {"matrix": n,
    "f32": n, "experts": n}}``: ``matrix`` the bfloat16 weights every tick
    reads whatever the routing, ``experts`` the held banks (2 matrices an
    expert: ``W_up``, ``W_down``), ``f32`` the float32 vectors."""
    t, dim = cfg.trunk, cfg.dim
    d_in, H = _d_in(cfg), t.ssd_heads
    inner = cfg.heads * cfg.dim_head
    fs = t.shared_dim or t.shared_experts * t.expert_dim
    return {
        "mamba2": {"matrix": dim * (d_in + _conv_dim(cfg) + H) + d_in * dim
                   + (t.ssm_conv + 1) * _conv_dim(cfg),
                   "f32": 3 * H + d_in + dim, "experts": 0},
        "none": {"matrix": 2 * dim * fs + dim * t.experts,
                 "f32": t.experts + dim,
                 "experts": 2 * t.held_experts * dim * t.expert_dim},
        "attention": {"matrix": dim * inner
                      + dim * 2 * t.kv_heads * cfg.dim_head + inner * dim,
                      "f32": dim, "experts": 0},
    }


def decode_weight_params(cfg) -> dict:
    """Parameters one tick must read, as ``{"matrix": n, "f32": n,
    "experts": n}``: every layer's weights (every held bank: at hundreds of
    rows a tick each held expert is chosen by some row), the head's image
    rows and the final norm (the embedding gathers ``rows`` rows of the
    table)."""
    per = layer_params(cfg)
    total = {"matrix": float(cfg.num_image_tokens * cfg.dim),
             "f32": float(cfg.dim), "experts": 0.0}
    for kind in _mixers(cfg):
        for key in total:
            total[key] += per[kind][key]
    return total


def decode_weight_bytes(cfg) -> float:
    p = decode_weight_params(cfg)
    return (p["matrix"] + p["experts"]) * MATRIX_BYTES + p["f32"] * F32_BYTES


def decode_kv_bytes(cfg, rows: float) -> float:
    """Bytes of keys and values one tick must read for ``rows`` rows,
    averaged over a request's ticks: a causal attention layer reaches every
    position up to the one it decodes (``text_seq_len + 1`` prompt positions
    and the image positions so far), k and v, ``kv_heads`` heads."""
    n_pre = cfg.text_seq_len + 1
    ticks = cfg.image_fmap_size ** 2 - 1
    reachable = n_pre + 1 + (ticks - 1) / 2.0      # mean of n_pre + 1 + j
    per_layer = reachable * 2 * cfg.trunk.kv_heads * cfg.dim_head * CACHE_BYTES
    return float(_mixers(cfg).count("attention") * per_layer * rows)


def tick_flops(cfg, rows: float) -> float:
    """FLOPs of one tick: 2 per weight and row for every weight a row uses
    (the routed banks: ``experts_per_token`` of ``experts`` chosen, of which
    the held share is computed here), the state updates' and the
    attention's."""
    t = cfg.trunk
    p = decode_weight_params(cfg)
    taps = _mixers(cfg).count("mamba2") * (t.ssm_conv + 1) * _conv_dim(cfg)
    used = t.experts_per_token / t.experts
    kv = decode_kv_bytes(cfg, rows)
    return (2.0 * (p["matrix"] - taps + used * p["experts"]) * rows
            + ssd_step_flops(cfg, rows)
            + kv / CACHE_BYTES * 2 * cfg.heads / t.kv_heads)


def _least(nbytes: float, flops: float, peaks: dict) -> dict:
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_flops = flops / peaks["bf16_flops"]
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "flops",
            "bytes": nbytes, "flops": flops}


def ssd_step_least_s(cfg, rows: float, peaks: dict) -> dict:
    """Least time of one tick's Mamba-2 state updates (decays, the state's
    update and read-out, ``D``, the gate and the grouped norm) over ``rows``
    rows."""
    return _least(ssd_step_bytes(cfg, rows), ssd_step_flops(cfg, rows), peaks)


def tick_least_s(cfg, rows: float, peaks: dict) -> dict:
    """Least time of one whole decode tick over ``rows`` rows: weights (every
    held bank), the head's image rows, the Mamba-2 layers' state and window
    traffic and the attention layers' reachable keys and values over the
    memory bandwidth, or the tick's FLOPs over the matrix peak if that is
    longer."""
    nbytes = (decode_weight_bytes(cfg) + ssd_step_bytes(cfg, rows)
              + window_bytes(cfg, rows) + decode_kv_bytes(cfg, rows))
    return _least(nbytes, tick_flops(cfg, rows), peaks)
