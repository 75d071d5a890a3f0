"""Operations and bytes the algorithms need, computed from shapes.

The yardstick's own arithmetic: later PRs may change the program's copies
(``utils/profiling.py``), not these.  Every function takes a ``DALLEConfig``
-like object (``dim``, ``depth``, ``heads``, ``dim_head``, ``text_seq_len``,
``image_fmap_size``, ``num_text_tokens``, ``num_image_tokens``,
``attn_types``).
"""
from __future__ import annotations

from benchmark.reference import pattern_mask

FF_MULT = 4


def _seq(cfg) -> int:
    return cfg.text_seq_len + cfg.image_fmap_size ** 2


def train_flops_per_image(cfg) -> float:
    """Forward + backward (3 x forward) matmul FLOPs of one trained image, in
    the dense-equivalent convention (copied from
    ``utils/profiling.py::dalle_train_flops``): attention counted dense at
    ``seq_len + 1`` positions whatever the pattern, the logits head as the
    two phase matmuls the loss really runs.  Recomputation does not count."""
    n, dim = _seq(cfg) + 1, cfg.dim
    inner = cfg.heads * cfg.dim_head
    per_layer = (2 * n * dim * 3 * inner          # qkv
                 + 2 * n * n * inner * 2          # scores and attn.v
                 + 2 * n * inner * dim            # out projection
                 + 2 * n * dim * FF_MULT * dim * 2    # GEGLU in
                 + 2 * n * FF_MULT * dim * dim)       # ff out
    head = 2 * dim * (
        cfg.text_seq_len * (cfg.num_text_tokens + cfg.text_seq_len)
        + cfg.image_fmap_size ** 2 * cfg.num_image_tokens)
    return 3.0 * (cfg.depth * per_layer + head)


def _variants(cfg) -> list:
    types = tuple(cfg.attn_types or ("full",))
    return [types[d % len(types)] for d in range(cfg.depth)]


def attention_train_least_s(cfg, batch_per_chip: float, peaks: dict,
                            act_bytes: int = 2) -> dict:
    """Least time one chip needs for the attention cores (scores, softmax,
    attn.v; not the projections) of one train step: forward + backward =
    3 x the forward's FLOPs on the (query, key) pairs each layer's pattern
    allows, and q, k, v, o read or written once forward and q, k, v, o, do,
    dq, dk, dv once backward.  Returns the bound and which peak sets it."""
    n, h, dh = _seq(cfg), cfg.heads, cfg.dim_head
    flops = nbytes = 0.0
    for variant in _variants(cfg):
        pairs = float(pattern_mask(variant, cfg.text_seq_len,
                                   cfg.image_fmap_size).sum())
        flops += 3 * 2 * 2 * pairs * h * dh
        nbytes += (4 + 8) * n * h * dh * act_bytes
    flops *= batch_per_chip
    nbytes *= batch_per_chip
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops >= t_bytes else "bytes",
            "flops": flops, "bytes": nbytes}


def decode_weight_bytes(cfg, param_bytes: int = 4) -> float:
    """Bytes of weights one decode tick must read: every layer's projections
    and GEGLU, and the image half of the logits head.  Parameters are stored
    in f32 (``param_bytes``)."""
    dim, inner = cfg.dim, cfg.heads * cfg.dim_head
    per_layer = (dim * 3 * inner + inner * dim
                 + dim * FF_MULT * dim * 2 + FF_MULT * dim * dim)
    return float(param_bytes * (cfg.depth * per_layer
                                + dim * cfg.num_image_tokens))


def decode_cache_bytes(cfg, rows: float, cache_bytes: int = 2) -> float:
    """Bytes of keys and values one decode tick must read for ``rows``
    sequences, averaged over the image positions: for each layer the keys its
    pattern lets a query reach (all text + the allowed earlier image
    positions), k and v."""
    t = cfg.text_seq_len + 1
    total = 0.0
    for variant in _variants(cfg):
        mask = pattern_mask(variant, cfg.text_seq_len, cfg.image_fmap_size)
        total += float(mask[t - 1:].sum(axis=1).mean())
    return total * 2 * cfg.heads * cfg.dim_head * cache_bytes * rows


def decode_tick_least_s(cfg, rows: float, peaks: dict) -> dict:
    """Least time of one decode tick over ``rows`` sequences: the bytes it
    must read over the memory bandwidth (a tick's FLOPs, 2 per weight and
    row, stay under that bound up to some hundred rows)."""
    nbytes = decode_weight_bytes(cfg) + decode_cache_bytes(cfg, rows)
    flops = 2.0 * decode_weight_bytes(cfg, 1) * rows
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_flops = flops / peaks["bf16_flops"]
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "flops",
            "bytes": nbytes, "flops": flops}
