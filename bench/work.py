"""Operations and bytes that the benchmark's work needs, from shapes alone.

Every per-layer roofline or utilization divides one of these by a time
read from the device trace. The counts are of what the algorithm needs,
not of what any implementation happens to do: a kernel that walks pages
it does not need, or a scan step that is masked out, does not raise them.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Published per-chip peaks for ``device_kind``; unknown kinds raise."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def roofline_s(flops: float, bytes_: float, pk: dict) -> float:
    """Least time the chip could take: the larger of compute and memory."""
    return max(flops / pk["bf16_flops_per_s"], bytes_ / pk["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# the paper's CNN (two 5x5 convs with 2x2 pools, fc -> fc_width -> classes)
# ---------------------------------------------------------------------------


def cnn_layer_flops(cfg: dict) -> dict:
    """Forward FLOPs per sample of each layer (multiply-add = 2)."""
    h, w, c = cfg["input_shape"]
    k, ch, fc, n_cls = cfg["kernel"], cfg["channels"], cfg["fc_width"], \
        cfg["num_classes"]
    flat = (h // 4) * (w // 4) * ch
    return {
        "conv1": 2 * h * w * ch * k * k * c,
        "conv2": 2 * (h // 2) * (w // 2) * ch * k * k * ch,
        "fc1": 2 * flat * fc,
        "fc2": 2 * fc * n_cls,
    }


def cnn_train_flops_per_sample(cfg: dict) -> float:
    """One SGD sample-step: forward, weight gradients, and input gradients
    of every layer but the first (the data needs no gradient)."""
    f = cnn_layer_flops(cfg)
    fwd = sum(f.values())
    return 3 * fwd - f["conv1"]


def cnn_param_count(cfg: dict) -> int:
    h, w, c = cfg["input_shape"]
    k, ch, fc, n_cls = cfg["kernel"], cfg["channels"], cfg["fc_width"], \
        cfg["num_classes"]
    flat = (h // 4) * (w // 4) * ch
    return (k * k * c * ch + ch + k * k * ch * ch + ch + flat * fc + fc
            + fc * n_cls + n_cls)


def vecavg_bytes(clients: int, d: int) -> int:
    """One server reduce: read the [C, D] f32 update matrix and the [C]
    weights, write the [D] f32 result and the [C] squared norms."""
    return 4 * (clients * d + clients + d + clients)


def vecavg_flops(clients: int, d: int) -> int:
    """Weighted sum plus squared norms: two multiply-adds per element."""
    return 4 * clients * d


# ---------------------------------------------------------------------------
# decoder transformer (starcoder2 family: GQA, window, gelu MLP, tied head)
# ---------------------------------------------------------------------------


def decoder_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies through, embedding lookup excluded and
    the tied LM head included."""
    d, f, L = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * (q + 2 * kv) + q * d + 2 * d * f
    return L * per_layer + d * cfg["vocab_size"]


def attended(cfg: dict, pos: int) -> int:
    """Keys a query at absolute position ``pos`` attends (itself included)."""
    w = cfg.get("sliding_window") or (pos + 1)
    return min(pos + 1, w)


def decoder_token_flops(cfg: dict, pos: int) -> float:
    """FLOPs of one token at position ``pos``: every matmul weight once,
    plus QK^T and PV over the keys in its window, in every layer."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    att = 4 * cfg["num_attention_heads"] * hd * attended(cfg, pos)
    return 2 * decoder_matmul_params(cfg) + cfg["num_hidden_layers"] * att


def prefill_flops(cfg: dict, plen: int) -> float:
    """A whole prompt of ``plen`` tokens (positions 0 .. plen-1). Only the
    last position needs the LM head."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    total = sum(decoder_token_flops(cfg, p) for p in range(plen))
    return total - (plen - 1) * 2 * d * V


def paged_attn_bytes(cfg: dict, pos: int, kv_bytes: int = 2,
                     out_bytes: int = 4) -> int:
    """Bytes one slot's decode attention needs in ONE layer when its new
    token sits at ``pos``: the K/V rows already in its window, the new
    K/V row read and written, the query read and the output written."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    rows = attended(cfg, pos) - 1
    return (2 * rows * hkv * hd * kv_bytes       # K and V rows read
            + 2 * 2 * hkv * hd * kv_bytes        # new K/V row in and out
            + hq * hd * kv_bytes                 # query
            + hq * hd * out_bytes)               # output


def paged_attn_flops(cfg: dict, pos: int) -> int:
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 4 * cfg["num_attention_heads"] * hd * attended(cfg, pos)
