"""Operations and bytes a decode tick of the ``glm-4.7-flash`` configuration
needs, computed from shapes: the yardstick's own arithmetic for the two
rooflines that configuration brings.  They count the work the mathematics
needs, whatever implements it: the latent a query can reach, in the absorbed
form (a tick decompresses no cached position); the banks of the held experts
that the routing must touch.  What implements the read must not change what
these count.

Every function takes a ``DALLEConfig``-like object with a latent, routed
``trunk`` (``dim``, ``depth``, ``heads``, ``text_seq_len``, ``image_fmap_size``,
``num_image_tokens``; ``trunk.q_rank``, ``kv_rank``, ``nope_dim``,
``rope_dim``, ``value_dim``, ``ff_dim``, ``dense_layers``, ``experts``,
``experts_held``, ``experts_per_token``, ``expert_dim``, ``shared_experts``).
Matrices, the expert banks, the head and the latent cache are bfloat16 (2
bytes), the norm gains and the selection bias float32.
"""
from __future__ import annotations

MATRIX_BYTES = 2
CACHE_BYTES = 2


def _held(t) -> int:
    return t.experts_held or t.experts


def latent_bytes_per_position(cfg) -> int:
    """What one layer's cache holds of one position: the normed latent and
    the one rotated key (1,152 bytes at 512 + 64 in bfloat16)."""
    t = cfg.trunk
    return (t.kv_rank + t.rope_dim) * CACHE_BYTES


def latent_flops_per_position(cfg) -> float:
    """FLOPs one layer's absorbed read spends on one cached position: every
    head's score over latent and rotary key, and its weighted sum of the
    latent (20 x (576 + 512) x 2 = 43,520)."""
    t = cfg.trunk
    return cfg.heads * (2.0 * t.kv_rank + t.rope_dim) * 2.0


def reachable_positions(cfg, n_prime: int, ticks: int) -> float:
    """Positions one row's query reaches in ONE layer, averaged over the
    ``ticks`` scan steps after a prompt of ``text_seq_len + 1 + n_prime``
    positions: step t decodes position ``p = n_pre + t`` and reaches ``p +
    1`` of them (3,201 at 2,049 + 2,303 ticks)."""
    n_pre = cfg.text_seq_len + 1 + n_prime
    return n_pre + 1 + (max(ticks, 1) - 1) / 2.0


def latent_read_bytes(cfg, rows: float, n_prime: int, ticks: int) -> float:
    """Bytes of latent one tick must read for ``rows`` rows, every layer."""
    return (cfg.depth * rows * reachable_positions(cfg, n_prime, ticks)
            * latent_bytes_per_position(cfg))


def latent_read_flops(cfg, rows: float, n_prime: int, ticks: int) -> float:
    return (cfg.depth * rows * reachable_positions(cfg, n_prime, ticks)
            * latent_flops_per_position(cfg))


def _least(nbytes: float, flops: float, peaks: dict) -> dict:
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_flops = flops / peaks["bf16_flops"]
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "flops",
            "bytes": nbytes, "flops": flops}


def mla_read_least_s(cfg, rows: float, n_prime: int, ticks: int,
                     peaks: dict) -> dict:
    """Least time of one tick's reads of the latent (every layer)."""
    return _least(latent_read_bytes(cfg, rows, n_prime, ticks),
                  latent_read_flops(cfg, rows, n_prime, ticks), peaks)


def attention_params(cfg) -> float:
    """One layer's five attention matrices (21.76M)."""
    t = cfg.trunk
    return float(cfg.dim * t.q_rank
                 + t.q_rank * cfg.heads * (t.nope_dim + t.rope_dim)
                 + cfg.dim * (t.kv_rank + t.rope_dim)
                 + t.kv_rank * cfg.heads * (t.nope_dim + t.value_dim)
                 + cfg.heads * t.value_dim * cfg.dim)


def expert_params(cfg) -> float:
    """One expert's three matrices (9.437M)."""
    return 3.0 * cfg.dim * cfg.trunk.expert_dim


def experts_touched(cfg, rows: float) -> float:
    """Held experts of one layer that ``rows`` rows of ``experts_per_token``
    choices each over all ``experts`` are expected to touch, every expert as
    likely as another: ``held (1 - (1 - k / E)^rows)`` (7.998 of 8 at 128
    rows x 4 of 64)."""
    t = cfg.trunk
    return _held(t) * (1.0 - (1.0 - t.experts_per_token / t.experts) ** rows)


def weight_params(cfg, rows: float) -> dict:
    """Parameters one tick must read, as ``{"matrix": n, "f32": n}``: every
    layer's attention, the dense layers' SwiGLU, each routed layer's router,
    shared experts and the held banks its rows touch, the head's image rows
    (the embedding gathers ``rows`` rows of the table); the norm gains and
    the selection bias."""
    t = cfg.trunk
    routed = cfg.depth - t.dense_layers
    matrix = (cfg.depth * attention_params(cfg)
              + t.dense_layers * 3.0 * cfg.dim * t.ff_dim
              + routed * (cfg.dim * t.experts
                          + (t.shared_experts + experts_touched(cfg, rows))
                          * expert_params(cfg))
              + cfg.num_image_tokens * cfg.dim)
    f32 = (cfg.depth * (2 * cfg.dim + t.q_rank + t.kv_rank)
           + routed * t.experts + cfg.dim)
    return {"matrix": float(matrix), "f32": float(f32)}


def weight_bytes(cfg, rows: float) -> float:
    """1.06 GB at 128 rows."""
    p = weight_params(cfg, rows)
    return p["matrix"] * MATRIX_BYTES + p["f32"] * 4


def weight_flops(cfg, rows: float) -> float:
    """FLOPs of a tick's products with weights: 2 a weight and row for what
    every row multiplies (attention, the dense SwiGLU, the router, the
    shared experts, the head's image rows), and for the routed experts each
    row's expected ``experts_per_token x held / experts`` of them."""
    t = cfg.trunk
    routed = cfg.depth - t.dense_layers
    every_row = (cfg.depth * attention_params(cfg)
                 + t.dense_layers * 3.0 * cfg.dim * t.ff_dim
                 + routed * (cfg.dim * t.experts
                             + t.shared_experts * expert_params(cfg))
                 + cfg.num_image_tokens * cfg.dim)
    chosen = routed * t.experts_per_token * _held(t) / t.experts
    return 2.0 * rows * (every_row + chosen * expert_params(cfg))


def tick_least_s(cfg, rows: float, n_prime: int, ticks: int,
                 peaks: dict) -> dict:
    """Least time of one whole decode tick over ``rows`` rows: the weights a
    tick must read and the reachable latent over the memory bandwidth, or
    the tick's FLOPs (the products with weights plus the absorbed reads')
    over the matrix peak if that is longer."""
    return _least(weight_bytes(cfg, rows)
                  + latent_read_bytes(cfg, rows, n_prime, ticks),
                  weight_flops(cfg, rows)
                  + latent_read_flops(cfg, rows, n_prime, ticks), peaks)
