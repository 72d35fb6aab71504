"""A configuration file (``bench/configs/<name>.json``) as numbers.

The file keeps the published ``config.json`` keys under ``published``;
this module reads the sizes the benchmark needs from them, so the file
is the one record of the configuration as it is run. The ``quant`` group
states the ICQuant settings, ``weight_seed`` the seed of the weights,
and ``check`` the limit of the correctness comparison.
"""
from __future__ import annotations

import dataclasses
import json


@dataclasses.dataclass(frozen=True)
class Model:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    sliding_window: int        # 0: full attention
    dtype: str                 # activations, KV and unquantized weights
    bits: int
    gamma: float
    method: str
    fmt: str
    weight_seed: int
    logit_gap_limit: float

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def itemsize(self) -> int:
        return {"bfloat16": 2, "float16": 2, "float32": 4}[self.dtype]

    def layer_linears(self):
        """(name, d_in, d_out) of each quantized matrix of one layer."""
        d, F = self.d_model, self.d_ff
        return (("wq", d, self.q_dim), ("wk", d, self.kv_dim),
                ("wv", d, self.kv_dim), ("wo", self.q_dim, d),
                ("w_gate", d, F), ("w_up", d, F), ("w_down", F, d))

    def linear_params(self) -> int:
        """Quantized weights of every layer, without the LM head."""
        per = sum(a * b for _, a, b in self.layer_linears())
        return self.n_layers * per

    def kv_row_bytes(self) -> int:
        """Bytes of K and V one token adds to the cache, all layers."""
        return 2 * self.n_layers * self.kv_dim * self.itemsize


def load(path: str) -> Model:
    with open(path) as f:
        d = json.load(f)
    p = d["published"]
    heads = p["num_attention_heads"]
    q = d["quant"]
    return Model(
        name=d["name"],
        n_layers=p["num_hidden_layers"],
        d_model=p["hidden_size"],
        n_heads=heads,
        n_kv_heads=p["num_key_value_heads"],
        head_dim=p.get("head_dim", p["hidden_size"] // heads),
        d_ff=p["intermediate_size"],
        vocab=p["vocab_size"],
        rope_theta=float(p["rope_theta"]),
        norm_eps=float(p["rms_norm_eps"]),
        sliding_window=int(p.get("sliding_window") or 0),
        dtype=p["torch_dtype"],
        bits=q["bits"],
        gamma=q["gamma"],
        method=q["method"],
        fmt=q["fmt"],
        weight_seed=d["weight_seed"],
        logit_gap_limit=float(d["check"]["logit_gap_limit"]),
    )


def program_config(m: Model):
    """The serving program's ``ModelConfig`` for these sizes."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=m.name, family="dense", n_layers=m.n_layers,
        d_model=m.d_model, n_heads=m.n_heads, n_kv_heads=m.n_kv_heads,
        head_dim=m.head_dim, d_ff=m.d_ff, vocab_size=m.vocab,
        rope_theta=m.rope_theta, norm_eps=m.norm_eps,
        sliding_window=m.sliding_window, param_dtype=m.dtype,
        tie_embeddings=False)
