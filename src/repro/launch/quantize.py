"""PTQ driver: calibrate Fisher sensitivity, then ICQuant every linear.

``python -m repro.launch.quantize --arch <id> --bits 2 --gamma 0.05``

Pipeline (mirrors paper Appendix E):
  1. train or load a model (smoke-size by default on this container);
  2. estimate diagonal Fisher information with 128 calibration sequences
     from the synthetic corpus (jax.grad of the LM loss);
  3. for every 2-D linear weight: ICQuant with per-output-channel
     partition, Fisher-weighted K-means (or RTN), gap-coded indices;
  4. emit bits/weight accounting + quantized params ready for serving.
"""
from __future__ import annotations

import argparse
from typing import Any, Dict, Optional, Set, Tuple

import jax
import jax.numpy as jnp

from repro.core import icquant
from repro.core.sensitivity import fisher_information, normalize_fisher
from repro.data import CalibrationSet, SyntheticLM
from repro.launch.steps import loss_fn

# leaves never quantized (norms, scalars, routers, SSD dynamics)
_SKIP_NAMES = {"router", "A_log", "D", "dt_bias", "conv_w", "conv_b",
               "q_norm", "kv_norm", "ln1", "ln2", "ln_cross", "norm",
               "final_norm", "enc_norm", "mtp_norm", "embed"}


def _leaf_name(path) -> str:
    return getattr(path[-1], "key", getattr(path[-1], "name", str(path[-1])))


def quantizable(path, leaf) -> bool:
    return (
        hasattr(leaf, "ndim") and leaf.ndim >= 2
        and _leaf_name(path) not in _SKIP_NAMES
    )


def compute_fisher(params, cfg, n_sequences: int = 128, seq_len: int = 256,
                   batch_size: int = 8):
    spec = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq_len)
    cal = CalibrationSet(spec, n_sequences=n_sequences, batch_size=batch_size)
    return fisher_information(
        lambda p, b: loss_fn(p, cfg, b)[0], params, cal.batches()
    )


def quantize_tree(
    params: Any,
    n_bits: int,
    gamma: float = 0.05,
    method: str = "rtn",
    fisher: Optional[Any] = None,
    b: Optional[int] = None,
) -> Tuple[Any, Dict[str, float]]:
    """Replace every quantizable 2-D (or expert/layer-stacked) weight with
    an ICQPacked. Stacked weights (L, d_in, d_out) / (L, E, d, f) are
    quantized per 2-D slice and restacked (the ICQPacked pytree keeps the
    leading axes). Returns (new_params, bits accounting)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    fisher_flat = None
    if fisher is not None:
        fisher_flat = jax.tree.leaves(fisher)

    out = []
    total_bits = 0.0
    total_weights = 0
    for i, (path, leaf) in enumerate(flat):
        if not quantizable(path, leaf):
            out.append(leaf)
            continue
        fw = fisher_flat[i] if fisher_flat is not None else None
        lead = leaf.shape[:-2]
        d_in, d_out = leaf.shape[-2], leaf.shape[-1]
        # per output channel = rows of W^T, transposed one slice at a
        # time (a whole-stack transpose would double the stack's memory)
        mats = leaf.reshape(-1, d_in, d_out)
        fmats = None if fw is None else fw.reshape(-1, d_in, d_out)
        packs = [
            icquant.quantize(
                mats[j].T, n_bits, gamma=gamma, b=b, method=method,
                fisher=None if fmats is None
                else normalize_fisher(fmats[j].T),
            )
            for j in range(mats.shape[0])
        ]
        # pad gap streams to a common width before stacking slices
        s_max = max(pk.symbols.shape[-1] for pk in packs)
        flag = (1 << packs[0].b) - 1
        packs = [
            pk if pk.symbols.shape[-1] == s_max
            else jax.tree.unflatten(
                jax.tree.structure(pk),
                [
                    jnp.pad(leafx, ((0, 0), (0, s_max - leafx.shape[-1])),
                            constant_values=flag)
                    if name == "symbols" else leafx
                    for name, leafx in zip(
                        ("codes", "symbols", "counts", "codebooks"),
                        jax.tree.leaves(pk),
                    )
                ],
            )
            for pk in packs
        ]
        packed = jax.tree.map(lambda *xs: jnp.stack(xs), *packs)
        if not lead:
            packed = jax.tree.map(lambda x: x[0], packed)
        else:
            # restore leading axes on the array leaves
            packed = jax.tree.map(
                lambda x: x.reshape(lead + x.shape[1:]), packed
            )
        bits = packs[0].bits_per_weight()["total"]
        total_bits += bits * leaf.size
        total_weights += leaf.size
        out.append(packed)

    new_params = jax.tree.unflatten(treedef, out)
    acct = dict(
        mean_bits=total_bits / max(total_weights, 1),
        quantized_weights=total_weights,
    )
    return new_params, acct


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--bits", type=int, default=2)
    ap.add_argument("--gamma", type=float, default=0.05)
    ap.add_argument("--method", choices=["rtn", "kmeans"], default="rtn")
    ap.add_argument("--no-fisher", action="store_true")
    args = ap.parse_args()

    from repro.configs import get_config, smoke_variant
    from repro.models import init_model

    cfg = smoke_variant(get_config(args.arch))
    params = init_model(jax.random.PRNGKey(0), cfg)
    fisher = None
    if args.method == "kmeans" and not args.no_fisher:
        fisher = compute_fisher(params, cfg, n_sequences=32, seq_len=64)
    qparams, acct = quantize_tree(
        params, args.bits, gamma=args.gamma, method=args.method, fisher=fisher
    )
    print(f"[quantize] {cfg.name}: {acct['mean_bits']:.3f} bits/weight over "
          f"{acct['quantized_weights']/1e6:.2f}M weights")


if __name__ == "__main__":
    main()
