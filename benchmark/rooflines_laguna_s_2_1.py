"""Operations and bytes a decode tick of the ``laguna-s-2.1`` configuration
needs, computed from shapes: the yardstick's own arithmetic for the two
rooflines that configuration brings.  They count the work the mathematics
needs, whatever implements it: the keys and values a query can reach (a
global layer every position up to its own, a window layer the last
``window`` of them); the banks of the held experts that the routing must
touch.  What implements the read must not change what these count.

Every function takes a ``DALLEConfig``-like object with a window-and-global,
routed ``trunk`` (``dim``, ``depth``, ``heads``, ``dim_head``,
``text_seq_len``, ``image_fmap_size``, ``num_image_tokens``, ``mixers``;
``trunk.kv_heads``, ``window``, ``window_heads``, ``ff_dim``,
``dense_layers``, ``experts``, ``experts_held``, ``experts_per_token``,
``expert_dim``, ``shared_experts``).  Matrices, the expert banks, the gate,
the head and the caches are bfloat16 (2 bytes), the norm gains float32.
"""
from __future__ import annotations

MATRIX_BYTES = 2
CACHE_BYTES = 2


def _held(t) -> int:
    return t.experts_held or t.experts


def _windowed(cfg):
    """Per layer, True for a window layer."""
    return [kind == "window" for kind in cfg.mixers]


def layer_heads(cfg, windowed: bool) -> int:
    """Query heads of a layer of that kind (72 window, 48 global)."""
    return (cfg.trunk.window_heads or cfg.heads) if windowed else cfg.heads


def kv_bytes_per_position(cfg) -> int:
    """What one layer's cache holds of one position: a key and a value of
    every key head (4,096 bytes at 8 heads of 128 in bfloat16)."""
    return 2 * cfg.trunk.kv_heads * cfg.dim_head * CACHE_BYTES


def kv_flops_per_position(cfg, windowed: bool) -> float:
    """FLOPs one layer's read spends on one reachable position: every query
    head's score and its share of the weighted sum (4 x 72 x 128 = 36,864
    in a window layer, 24,576 in a global one)."""
    return 4.0 * layer_heads(cfg, windowed) * cfg.dim_head


def reachable_positions(cfg, windowed: bool, n_prime: int,
                        ticks: int) -> float:
    """Positions one row's query reaches in ONE layer, averaged over the
    ``ticks`` scan steps after a prompt of ``text_seq_len + 1 + n_prime``
    positions: step t decodes position ``p = n_pre + t`` and reaches ``p +
    1`` of them in a global layer (3,201 at 2,049 + 2,303 ticks), ``min(p +
    1, window)`` in a window layer (512 throughout)."""
    n_pre = cfg.text_seq_len + 1 + n_prime
    ticks = max(ticks, 1)
    if not windowed:
        return n_pre + 1 + (ticks - 1) / 2.0
    w = cfg.trunk.window
    return sum(min(n_pre + 1 + t, w) for t in range(ticks)) / ticks


def kv_read_bytes(cfg, rows: float, n_prime: int, ticks: int) -> float:
    """Bytes of keys and values one tick must read for ``rows`` rows, every
    layer (3.12 GB at 96 rows)."""
    return rows * kv_bytes_per_position(cfg) * sum(
        reachable_positions(cfg, w, n_prime, ticks) for w in _windowed(cfg))


def kv_read_flops(cfg, rows: float, n_prime: int, ticks: int) -> float:
    return rows * sum(
        reachable_positions(cfg, w, n_prime, ticks)
        * kv_flops_per_position(cfg, w) for w in _windowed(cfg))


def _least(nbytes: float, flops: float, peaks: dict) -> dict:
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_flops = flops / peaks["bf16_flops"]
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "flops",
            "bytes": nbytes, "flops": flops}


def read_least_s(cfg, rows: float, n_prime: int, ticks: int,
                 peaks: dict) -> dict:
    """Least time of one tick's reads of the reachable keys and values
    (every layer)."""
    return _least(kv_read_bytes(cfg, rows, n_prime, ticks),
                  kv_read_flops(cfg, rows, n_prime, ticks), peaks)


def attention_params(cfg, windowed: bool) -> float:
    """One layer's queries, keys and values, gate and output (44.19M
    global, 63.14M window)."""
    h, t = layer_heads(cfg, windowed), cfg.trunk
    return float(2 * cfg.dim * h * cfg.dim_head
                 + 2 * cfg.dim * t.kv_heads * cfg.dim_head + cfg.dim * h)


def expert_params(cfg) -> float:
    """One expert's three matrices (9.437M)."""
    return 3.0 * cfg.dim * cfg.trunk.expert_dim


def experts_touched(cfg, rows: float) -> float:
    """Held experts of one layer that ``rows`` rows of ``experts_per_token``
    choices each over all ``experts`` are expected to touch, every expert as
    likely as another: ``held (1 - (1 - k / E)^rows)`` (15.65 of 16 at 96
    rows x 10 of 256)."""
    t = cfg.trunk
    return _held(t) * (1.0 - (1.0 - t.experts_per_token / t.experts) ** rows)


def weight_params(cfg, rows: float) -> dict:
    """Parameters one tick must read, as ``{"matrix": n, "f32": n}``: every
    layer's attention and gate, the dense layers' SwiGLU, each routed
    layer's router, shared experts and the held banks its rows touch, the
    head's image rows (the embedding gathers ``rows`` rows of the table);
    the norm gains."""
    t = cfg.trunk
    routed = cfg.depth - t.dense_layers
    matrix = (sum(attention_params(cfg, w) for w in _windowed(cfg))
              + t.dense_layers * 3.0 * cfg.dim * t.ff_dim
              + routed * (cfg.dim * t.experts
                          + (t.shared_experts + experts_touched(cfg, rows))
                          * expert_params(cfg))
              + cfg.num_image_tokens * cfg.dim)
    f32 = cfg.depth * 2 * cfg.dim + cfg.dim
    return {"matrix": float(matrix), "f32": float(f32)}


def weight_bytes(cfg, rows: float) -> float:
    """2.10 GB at 96 rows."""
    p = weight_params(cfg, rows)
    return p["matrix"] * MATRIX_BYTES + p["f32"] * 4


def weight_flops(cfg, rows: float) -> float:
    """FLOPs of a tick's products with weights: 2 a weight and row for what
    every row multiplies (attention and gate, the dense SwiGLU, the router,
    the shared experts, the head's image rows), and for the routed experts
    each row's expected ``experts_per_token x held / experts`` of them."""
    t = cfg.trunk
    routed = cfg.depth - t.dense_layers
    every_row = (sum(attention_params(cfg, w) for w in _windowed(cfg))
                 + t.dense_layers * 3.0 * cfg.dim * t.ff_dim
                 + routed * (cfg.dim * t.experts
                             + t.shared_experts * expert_params(cfg))
                 + cfg.num_image_tokens * cfg.dim)
    chosen = routed * t.experts_per_token * _held(t) / t.experts
    return 2.0 * rows * (every_row + chosen * expert_params(cfg))


def tick_least_s(cfg, rows: float, n_prime: int, ticks: int,
                 peaks: dict) -> dict:
    """Least time of one whole decode tick over ``rows`` rows: the weights a
    tick must read and the reachable keys and values over the memory
    bandwidth, or the tick's FLOPs (the products with weights plus the
    reads') over the matrix peak if that is longer (6.37 ms at 96 rows)."""
    return _least(weight_bytes(cfg, rows)
                  + kv_read_bytes(cfg, rows, n_prime, ticks),
                  weight_flops(cfg, rows)
                  + kv_read_flops(cfg, rows, n_prime, ticks), peaks)
