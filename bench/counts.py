"""Operations and bytes, worked out from shapes alone.

Per configuration family: the parameter count ``P`` and the operations a
learner's training step and evaluation need (multiply-adds count two;
recomputation under rematerialisation does not count; causal attention
counts the half of the score matrix it needs); each family module in
``bench/configs/`` names its own as ``learner_flops``.  Per kernel: the
bytes the reduction and the int8 quantizer must move and the operations
they must do.
"""

from __future__ import annotations

import dataclasses

from bench import spec


def mlp_params(c: dict) -> int:
    w, layers = int(c["width"]), int(c["n_hidden_layers"])
    f, o = int(c["n_features"]), int(c["n_outputs"])
    return (f * w + w) + (layers - 1) * (w * w + w) + (w * o + o)


def mlp_matmul_params(c: dict) -> int:
    w, layers = int(c["width"]), int(c["n_hidden_layers"])
    return int(c["n_features"]) * w + (layers - 1) * w * w + w * int(c["n_outputs"])


def lm_layer_params(c: dict) -> int:
    d, f = int(c["hidden_size"]), int(c["intermediate_size"])
    h, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd = d // h
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d + (h + 2 * kv) * hd
    return attn + 3 * d * f + 2 * d


def lm_params(c: dict) -> int:
    d, v = int(c["hidden_size"]), int(c["vocab_size"])
    head = 0 if c["tie_word_embeddings"] else v * d
    return v * d + head + int(c["num_hidden_layers"]) * lm_layer_params(c) + d


def lm_matmul_params(c: dict) -> int:
    """Weights a token multiplies through: every layer's matrices and the head."""
    d, f = int(c["hidden_size"]), int(c["intermediate_size"])
    h, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd = d // h
    per_layer = 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f
    return int(c["num_hidden_layers"]) * per_layer + int(c["vocab_size"]) * d


def lm_attention_flops_fwd(c: dict, seq: int) -> int:
    """Per token, forward: QK^T and PV over the causal half of the context."""
    d = int(c["hidden_size"])
    return int(c["num_hidden_layers"]) * 2 * 2 * d * seq // 2


def mlp_learner_flops(c: dict, t: dict) -> dict:
    """Operations of one MLP learner task: ``train`` (all local steps), ``eval``."""
    fwd = 2 * mlp_matmul_params(c)
    f = spec.federation(t)
    return {"train": 3 * fwd * int(f["batch_size"]) * int(f["local_steps"]),
            "eval": fwd * int(t["eval_batch"])}


def lm_learner_flops(c: dict, t: dict) -> dict:
    """Operations of one decoder learner task: ``train`` (all local steps), ``eval``."""
    seq = int(t["seq_len"])
    fwd = 2 * lm_matmul_params(c) + lm_attention_flops_fwd(c, seq)
    f = spec.federation(t)
    return {"train": 3 * fwd * int(f["batch_size"]) * seq * int(f["local_steps"]),
            "eval": fwd * int(t["eval_batch"]) * seq}


def reduce_cost(rows: int, width: int, arena_dtype: str, group: int = 256) -> dict:
    """The masked weighted mean over an ``(rows, width)`` arena into one row."""
    if arena_dtype == "int8":
        read = rows * width + rows * (width // group) * 4
        flops = 3 * rows * width  # dequantize multiply, then multiply-add
    else:
        read = rows * width * 4
        flops = 2 * rows * width
    return {"bytes": read + width * 4, "flops": flops}


def quantize_cost(width: int, group: int = 256) -> dict:
    """Symmetric int8 quantization of one f32 row: read it, write q and scales."""
    return {"bytes": width * 4 + width + (width // group) * 4, "flops": 3 * width}


def roofline_s(cost: dict, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(cost["bytes"] / float(peaks["hbm_bytes_per_s"]),
               cost["flops"] / float(peaks["bf16_flops_per_s"]))


@dataclasses.dataclass(frozen=True)
class Shapes:
    """The arena's shape as the run built it: ``width`` columns, split
    into ``shards`` equal column shards, one a device."""

    rows: int
    width: int
    arena_dtype: str
    shards: int = 1

    @property
    def shard_width(self) -> int:
        return self.width // self.shards

    @classmethod
    def of(cls, fed) -> "Shapes":
        arena = fed.controller.arena
        return cls(rows=int(arena.buffer.shape[0]), width=int(arena.padded_params),
                   arena_dtype=str(arena.arena_dtype), shards=int(arena.n_shards))
