"""Leaf by leaf, where a cell's gap to its float32 reference lies: a look, at
a cell's own size and on several seeds, that ``chipbench.readings`` does not
take (it gives the worst leaf and the whole tree, not which leaf).

    python3 scripts/leaf_gap_look.py --workload xing4_0_29b_a4b.single_build \
        --seeds <a>,<b> --out chiprun_out/leaves.jsonl [--manifest <file>] [--rehearsal]

One process. Every seed's ``build()`` goes through the timed path first (the
chunk program compiles once), then the program is released and each seed's
machine is built by the plain reference and compared. A line of JSON a seed:

``numbers``   what :mod:`chipbench.check` reads (``leaf`` and ``weights`` among them)
``groups``    the ``weights`` gap by group of leaves (the held experts, the
              shared expert, the router, the streams' mixing, the latent
              projections, a dense FFN, everything else): each group's gap,
              its share of the squared gap and of the reference's squared change
``leaves``    every leaf: whether ``check`` counts it, the norm of the
              reference's change and of the program's, ``leaf_gap`` as ``check``
              computes it, and the leaf's own ``weights`` gap
``losses``    the reference's final epoch loss, the program's, and the mean
              squared error on the probe rows of the reference's model before
              its first step and after its last (what an Adam rate has to lower)

Not a test and not the benchmark: a builder's instrument (PERF.md section 6).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

LATENT = ("w_dq", "w_uq", "w_dkv", "w_ukv", "wo", "q_norm", "kv_norm")


def group_of(key: str, ndim: int) -> str:
    if key in ("w1", "w2", "w3"):
        return "experts" if ndim == 3 else "dense_ffn"
    if key.startswith("shared_"):
        return "shared_expert"
    if key in ("router", "expert_bias"):
        return "router"
    if key.startswith("hc_"):
        return "mixing"
    return "latent" if key in LATENT else "rest"


def by_leaf(observed_params, ref) -> dict:
    """``check.gaps``' walk over the leaves, with every leaf's numbers kept."""
    leaves = []
    for i, layer in enumerate(ref["init"]):
        for key in sorted(layer):
            init = np.asarray(layer[key], np.float64)
            d_ref = np.asarray(ref["params"][i][key], np.float64) - init
            d_obs = np.asarray(observed_params[i][key], np.float64) - init
            leaves.append({
                "layer": i, "leaf": key, "size": int(init.size),
                "group": group_of(key, init.ndim),
                "first_grad": float(np.linalg.norm(np.asarray(ref["first_grad"][i][key], np.float64))),
                "reference_change": float(np.linalg.norm(d_ref)),
                "observed_change": float(np.linalg.norm(d_obs)),
                "diff_sq": float(np.sum((d_obs - d_ref) ** 2)),
            })
    grads = np.array([leaf["first_grad"] for leaf in leaves])
    median_change = float(np.median([leaf["reference_change"] for leaf in leaves]))
    groups = {}
    for leaf, grad in zip(leaves, grads):
        leaf["counted"] = bool(grad >= 1e-3 * np.median(grads))
        ref_change = leaf["reference_change"]
        leaf["leaf_gap"] = abs(leaf["observed_change"] - ref_change) / max(ref_change, median_change)
        leaf["gap"] = float(np.sqrt(leaf["diff_sq"]) / ref_change) if ref_change else None
        if leaf["counted"]:
            g = groups.setdefault(leaf["group"], [0.0, 0.0])
            g[0] += leaf["diff_sq"]
            g[1] += ref_change**2
    diff, base = (sum(g[j] for g in groups.values()) for j in (0, 1))
    return {
        "median_reference_change": median_change,
        "groups": {
            name: {
                "gap": float(np.sqrt(g[0] / g[1])),
                "share_of_squared_gap": g[0] / diff,
                "share_of_squared_change": g[1] / base,
            }
            for name, g in groups.items()
        },
        "leaves": leaves,
    }


def probe_losses(config: dict, ref: dict, frame: np.ndarray, rows: int) -> dict:
    """The reference's model on the probe rows, before its first step and
    after its last: the compiled prediction of the final stage, called again."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference

    model = config["model"]
    lookback = int(model["lookback_window"])
    _, predict = reference._step_fn(
        config["reference"], json.dumps(model, sort_keys=True),
        json.dumps(config["optimizer"], sort_keys=True), "float32",
    )
    start, end = reference.fold_bounds(rows, int(config["cv_splits"]))[-1][1], rows
    starts = jnp.arange(end - start - lookback + 1) + start
    scaled = jnp.asarray(reference._minmax_scale(frame, rows))[None]
    target = frame[start + lookback - 1 : end]
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda a: jnp.asarray(a)[None], ref["init"])
        untrained = np.asarray(predict(params, scaled, starts))[0]
    return {
        "probe_mse_untrained": float(np.mean((untrained - target) ** 2)),
        "probe_mse_trained": float(np.mean((np.asarray(ref["output"]) - target) ** 2)),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--out", required=True)
    parser.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    from chipbench import check, reference
    from chipbench.run import Fleet, load_cell, observe_artifact, release_program, sample, set_up

    cell = load_cell(args.manifest, args.workload)
    config, tr = cell["config"], cell["traffic"]
    devices = set_up(cell, args.rehearsal)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    out_root = tempfile.mkdtemp(prefix="leaf-gap-look-")
    try:
        built = []
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.time()
            build = Fleet(cell, seed, os.path.join(out_root, str(seed))).build(tr.chunk_machines)
            names, paths, frames, probe = sample(cell, seed, [build])
            built.append((seed, names, paths[0], frames, probe, time.time() - t0))
            print(f"leaf_gap_look: seed {seed} built in {built[-1][-1]:.1f} s", file=sys.stderr, flush=True)
        release_program(devices)
        for seed, names, path, frames, probe, build_s in built:
            t0 = time.time()
            ref = reference.build_machines(config, names, frames, seed)[0]
            reference_s = time.time() - t0
            observed = observe_artifact(path, frames[0], probe)
            line = {
                "workload": args.workload, "seed": seed, "build_s": build_s,
                "reference_s": reference_s,
                "numbers": check.gaps(observed, ref, frames[0][probe]),
                "losses": dict(
                    reference_epoch_loss=ref["loss"], program_epoch_loss=observed["loss"],
                    **probe_losses(config, ref, frames[0], tr.rows),
                ),
                **by_leaf(observed["params"], ref),
            }
            with open(args.out, "a") as sink:
                sink.write(json.dumps(line) + "\n")
            worst = sorted(
                (leaf for leaf in line["leaves"] if leaf["counted"]),
                key=lambda leaf: -leaf["leaf_gap"],
            )[:8]
            print(json.dumps({
                k: line[k] for k in ("seed", "reference_s", "numbers", "losses", "groups")
            }), file=sys.stderr)
            print(json.dumps([
                {k: leaf[k] for k in ("layer", "leaf", "leaf_gap", "gap", "reference_change")}
                for leaf in worst
            ]), file=sys.stderr, flush=True)
            del ref, observed
    finally:
        shutil.rmtree(out_root, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
