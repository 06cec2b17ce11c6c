"""The sizes of one configuration file, as the benchmark's own code reads
them: the seeded weights, the plain reference and the operation counts
all take their shapes from here and never from the program."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    layers: int
    d_model: int
    d_ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    tied: bool
    norm: str            # "layernorm" | "rmsnorm"
    norm_eps: float
    rope_theta: float

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    def layer_matrix_params(self) -> int:
        """Weights of one layer's matrices (attention and gated MLP)."""
        d = self.d_model
        return (d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
                + 3 * d * self.d_ff)

    def layer_params(self) -> int:
        norm = 2 if self.norm == "layernorm" else 1
        return self.layer_matrix_params() + 2 * norm * self.d_model

    def param_count(self) -> int:
        norm = 2 if self.norm == "layernorm" else 1
        emb = self.vocab * self.d_model
        head = 0 if self.tied else emb
        return (emb + head + self.layers * self.layer_params()
                + norm * self.d_model)


def dims(conf: dict) -> Dims:
    heads = int(conf["num_attention_heads"])
    d = int(conf["hidden_size"])
    return Dims(
        name=conf["name"], layers=int(conf["num_hidden_layers"]), d_model=d,
        d_ff=int(conf["intermediate_size"]), heads=heads,
        kv_heads=int(conf.get("num_key_value_heads", heads)),
        head_dim=int(conf.get("head_dim", d // heads)),
        vocab=int(conf["vocab_size"]),
        tied=bool(conf.get("tie_word_embeddings", False)),
        norm=conf["norm"], norm_eps=float(conf["norm_eps"]),
        rope_theta=float(conf.get("rope_theta", 10000.0)))
