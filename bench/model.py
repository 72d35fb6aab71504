"""A configuration file (``bench/configs/<name>.json``) as numbers.

The file keeps the published ``config.json`` keys under ``published``;
this module reads from them the sizes every architecture has, and finds
the architecture's module by the name ``published.architectures[0]``
(``bench/archs/<module>.py``, see ``bench/manifest.py``), which reads
its block's own sizes. So the file is the one record of the
configuration as it is run. The ``quant`` group states the ICQuant
settings, ``weight_seed`` the seed of the weights, and ``check`` the
limit of the correctness comparison.
"""
from __future__ import annotations

import dataclasses
import json
import types

from bench import manifest


@dataclasses.dataclass(frozen=True)
class Model:
    name: str
    n_layers: int
    d_model: int
    vocab: int
    norm_eps: float
    dtype: str                 # activations, KV and unquantized weights
    bits: int
    gamma: float
    method: str
    fmt: str
    weight_seed: int
    logit_gap_limit: float
    arch: types.ModuleType     # the module that serves the architecture
    block: object              # its sizes, as ``arch.block`` reads them

    @property
    def itemsize(self) -> int:
        return {"bfloat16": 2, "float16": 2, "float32": 4}[self.dtype]


def load(path: str, root: str = manifest.ROOT) -> Model:
    """The configuration in ``path``, its architecture found among the
    modules of ``<root>/bench/archs``."""
    with open(path) as f:
        d = json.load(f)
    p = d["published"]
    arch = manifest.architecture(manifest.architecture_name(d, path), root)
    q = d["quant"]
    return Model(
        name=d["name"],
        n_layers=p["num_hidden_layers"],
        d_model=p["hidden_size"],
        vocab=p["vocab_size"],
        norm_eps=float(p["rms_norm_eps"]),
        dtype=p["torch_dtype"],
        bits=q["bits"],
        gamma=q["gamma"],
        method=q["method"],
        fmt=q["fmt"],
        weight_seed=d["weight_seed"],
        logit_gap_limit=float(d["check"]["logit_gap_limit"]),
        arch=arch,
        block=arch.block(p),
    )
