"""Operations and bytes that the work of a cell requires, computed from the
configuration's sizes, and the chip peaks they are set against.

What is counted is what the mathematics needs, whatever implements it:
recomputation, padding and masked-out tiles are not counted, so a kernel
that visits masked tiles, or a step that recomputes its forward pass,
shows as a lower share of its roofline."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

from model_conf import Dims

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: "
                       f"{sorted(k for k in table if k != 'source')}")
    return table[device_kind]


def causal_pairs(seq: int) -> int:
    """(query, key) pairs a causal mask keeps over ``seq`` positions."""
    return seq * (seq + 1) // 2


def attention_flops_fwd(dm: Dims, batch: int, seq: int) -> float:
    """Causal attention forward, all layers: Q K^T and P V over the kept
    pairs, 2 operations per multiply-add."""
    return 2 * 2 * dm.head_dim * causal_pairs(seq) * dm.heads * batch * dm.layers


def attention_flops_train(dm: Dims, batch: int, seq: int) -> float:
    """Forward (2 products) and backward (dV, dP, dQ, dK: 4 products) of
    causal attention, with no recomputation counted."""
    return 3 * attention_flops_fwd(dm, batch, seq)


def attention_bytes_train(dm: Dims, batch: int, seq: int,
                          itemsize: int = 2) -> float:
    """Least HBM traffic of attention in training, all layers: the forward
    reads Q, K, V and writes O; the backward reads Q, K, V, O, dO and
    writes dQ, dK, dV."""
    q = batch * seq * dm.q_dim * itemsize
    kv = batch * seq * dm.kv_dim * itemsize
    fwd = 2 * q + 2 * kv
    bwd = 3 * q + 2 * kv + q + 2 * kv
    return (fwd + bwd) * dm.layers


def dense_params(dm: Dims) -> int:
    """Weights every token multiplies by: the layers' matrices and the
    output head (the embedding lookup does no arithmetic)."""
    return dm.layers * dm.layer_matrix_params() + dm.d_model * dm.vocab


def train_flops_per_step(dm: Dims, batch: int, seq: int) -> float:
    """6 N per token over the dense weights and the head, plus causal
    attention, forward and backward, with nothing recomputed."""
    return (6.0 * dense_params(dm) * batch * seq
            + attention_flops_train(dm, batch, seq))


def decode_flops(dm: Dims, active: int, kv_positions: int) -> float:
    """One decode step: 2 N per active sequence, plus attention of each
    new token over the positions it attends (``kv_positions`` summed over
    the active sequences)."""
    return (2.0 * dense_params(dm) * active
            + 2 * 2 * dm.head_dim * dm.heads * dm.layers * kv_positions)


def decode_bytes(dm: Dims, kv_positions: int, itemsize: int = 2) -> float:
    """One decode step reads every weight once (the tied or untied output
    head included; the embedding lookup reads a row per token) and the
    keys and values of the positions it attends."""
    lookup_only = 0 if dm.tied else dm.vocab * dm.d_model
    weights = (dm.param_count() - lookup_only) * itemsize
    kv = 2 * dm.kv_dim * dm.layers * kv_positions * itemsize
    return weights + kv


def least_time(flops: float, nbytes: float, peak: Dict[str, float]):
    """(seconds, bound) of the larger of compute and memory time."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
