"""Operations and bytes a decode tick of the ``smallthinker-21ba3b``
configuration needs, computed from shapes: the yardstick's own arithmetic for
the two rooflines that configuration brings.  They count the work the
mathematics needs, whatever implements it: the experts the routing must touch,
the keys a query can reach.

Every function takes a ``DALLEConfig``-like object with a routed ``trunk``
(``dim``, ``depth``, ``heads``, ``dim_head``, ``text_seq_len``,
``image_fmap_size``, ``num_image_tokens``; ``trunk.mixers``, ``kv_heads``,
``window``, ``experts``, ``experts_per_token``, ``expert_dim``).  Matrices, the
expert banks, the head and the key/value caches are bfloat16 (2 bytes), the
norm gains float32.
"""
from __future__ import annotations

MATRIX_BYTES = 2
CACHE_BYTES = 2


def _mixers(cfg) -> list:
    m = cfg.trunk.mixers
    return [m[i % len(m)] for i in range(cfg.depth)]


def experts_touched(cfg, rows: float) -> float:
    """Experts of one layer that ``rows`` rows of ``experts_per_token``
    choices each are expected to touch, every expert as likely as another:
    ``E (1 - (1 - k / E)^rows)`` (63.9 of 64 at 64 rows x 6; 6 at one row)."""
    t = cfg.trunk
    return t.experts * (1.0 - (1.0 - t.experts_per_token / t.experts) ** rows)


def expert_bank_bytes(cfg) -> float:
    """One expert's three matrices."""
    return 3.0 * cfg.dim * cfg.trunk.expert_dim * MATRIX_BYTES


def moe_layer_bytes(cfg, rows: float) -> float:
    """Bytes one expert layer must move for ``rows`` rows: the banks of the
    experts touched, the rows' activations in and out."""
    return (experts_touched(cfg, rows) * expert_bank_bytes(cfg)
            + 2.0 * rows * cfg.dim * MATRIX_BYTES)


def moe_layer_flops(cfg, rows: float) -> float:
    """FLOPs of the same: each row through its ``experts_per_token`` experts'
    three products (2 a weight)."""
    t = cfg.trunk
    return rows * t.experts_per_token * 3.0 * 2.0 * cfg.dim * t.expert_dim


def _least(nbytes: float, flops: float, peaks: dict) -> dict:
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_flops = flops / peaks["bf16_flops"]
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "flops",
            "bytes": nbytes, "flops": flops}


def moe_experts_least_s(cfg, rows: float, peaks: dict) -> dict:
    """Least time of one tick's expert layers (every layer is one)."""
    return _least(cfg.depth * moe_layer_bytes(cfg, rows),
                  cfg.depth * moe_layer_flops(cfg, rows), peaks)


def other_weight_params(cfg) -> dict:
    """Parameters one tick must read besides the expert banks, as
    ``{"matrix": n, "f32": n}``: every layer's attention projections and
    router, the norm gains, and the head's image rows (the embedding gathers
    ``rows`` rows of the table)."""
    inner = cfg.heads * cfg.dim_head
    attn = (cfg.dim * inner + cfg.dim * 2 * cfg.trunk.kv_heads * cfg.dim_head
            + inner * cfg.dim)
    router = cfg.dim * cfg.trunk.experts
    return {"matrix": float(cfg.depth * (attn + router)
                            + cfg.num_image_tokens * cfg.dim),
            "f32": float(cfg.depth * 2 * cfg.dim + cfg.dim)}


def other_weight_bytes(cfg) -> float:
    p = other_weight_params(cfg)
    return p["matrix"] * MATRIX_BYTES + p["f32"] * 4


def reachable_keys(cfg, n_prime: int, ticks: int) -> float:
    """Keys one row's query can reach, summed over the layers and averaged
    over the ``ticks`` scan steps after a prompt of ``text_seq_len + 1 +
    n_prime`` positions: step t decodes position ``p = n_pre + t`` and a
    global layer reaches ``p + 1`` keys, a window layer ``min(window, p +
    1)``."""
    n_pre = cfg.text_seq_len + 1 + n_prime
    total = 0.0
    for kind in _mixers(cfg):
        for t in range(ticks):
            keys = n_pre + t + 1
            total += min(cfg.trunk.window, keys) if kind == "window" else keys
    return total / max(ticks, 1)


def decode_kv_bytes(cfg, rows: float, n_prime: int, ticks: int) -> float:
    """Bytes of keys and values one tick must read for ``rows`` rows."""
    return (reachable_keys(cfg, n_prime, ticks) * 2 * cfg.trunk.kv_heads
            * cfg.dim_head * CACHE_BYTES * rows)


def tick_least_s(cfg, rows: float, n_prime: int, ticks: int,
                 peaks: dict) -> dict:
    """Least time of one whole decode tick over ``rows`` rows: the touched
    expert banks, the other weights, the head's image rows and the reachable
    keys and values over the memory bandwidth, or the tick's FLOPs (2 per
    matrix weight used and row, plus attention's) over the matrix peak if
    that is longer."""
    kv = decode_kv_bytes(cfg, rows, n_prime, ticks)
    nbytes = (cfg.depth * moe_layer_bytes(cfg, rows) + other_weight_bytes(cfg)
              + kv)
    flops = (cfg.depth * moe_layer_flops(cfg, rows)
             + 2.0 * other_weight_params(cfg)["matrix"] * rows
             + kv / CACHE_BYTES * 2 * cfg.heads / cfg.trunk.kv_heads)
    return _least(nbytes, flops, peaks)
