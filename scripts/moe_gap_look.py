"""Where a routed configuration's gap to its float32 reference lies: a look,
at a cell's own size, that ``chipbench.readings`` does not take.

    python3 scripts/moe_gap_look.py --workload lfm2_8b_a1b.single_build \
        --seed <n> --out chiprun_out/look.jsonl [--forced] [--rehearsal --manifest <file>]

One process, one machine, a line of JSON a stage. The grouped products are first
held to a dense float32 product at the cell's shapes, forward and both
gradients (what no CPU test compiles for the chip). Then one ``build()``
through the timed path and the plain reference three ways: float32, with
bfloat16 operands (the stated precision in the plain model: what rounding
alone costs, no kernel, no sort), and with ``--forced`` with bfloat16
operands under the routing a float32 pass over the same weights decides
(rounding without its routing flips). Each is put in the program's place
against the float32 reference:

``numbers``    what :mod:`chipbench.check` reads
``groups``     the ``weights`` gap by group of leaves: the held experts'
               matrices, the routers, everything else; each group's share of
               the squared gap and of the reference's squared change
``experts``    the experts' gap expert by expert and layer by layer, beside
               the norm of the reference's change of that expert
``windows``    the ``output`` gap window by window of the probe rows
``flips``      on the probe windows with the float32 reference's trained
               weights: token-layers whose selection differs between float32
               and bfloat16 operands, how many of those touch a held expert,
               and the ``output`` gap of the bfloat16 pass with its own
               routing and with the float32 pass's

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
import types

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def grouped_products_against_dense(model: dict, tokens: int) -> dict:
    """``grouped_matmul`` and its two gradients at a routed layer's shapes,
    bfloat16 operands, against the same sums made densely in float32 at
    ``highest``: the worst gap over the rms of the dense answer. Groups of
    uneven size, one empty, and rows past the live count."""
    import jax
    import jax.numpy as jnp

    from gordo_tpu.ops.nn import grouped_matmul

    held, d, f = (int(model[k]) for k in ("experts_held", "d_model", "expert_dim"))
    rows = tokens * int(model["top_k"])
    key = jax.random.PRNGKey(35)
    kx, kw, kg = jax.random.split(key, 3)
    share = np.array([0.55, 0.0, 0.02, 0.1, 0.08, 0.0005, 0.03, 0.02])[:held]
    sizes = np.maximum((share * rows * 0.3).astype(np.int32), 0)
    x = jax.random.normal(kx, (rows, d), jnp.float32)
    w = 0.02 * jax.random.normal(kw, (held, d, f), jnp.float32)
    dy = jax.random.normal(kg, (rows, f), jnp.float32)
    group = np.repeat(np.arange(held + 1), list(sizes) + [rows - int(sizes.sum())])

    def fast(x, w):
        y = grouped_matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), jnp.asarray(sizes))
        return jnp.sum(y.astype(jnp.float32) * dy), y

    def dense(x, w):
        xr = x.astype(jnp.bfloat16).astype(jnp.float32)
        wr = w.astype(jnp.bfloat16).astype(jnp.float32)
        y = jnp.zeros((rows, f), jnp.float32)
        for e in range(held):
            mask = jnp.asarray(group == e, jnp.float32)[:, None]
            y = y + mask * jnp.matmul(xr, wr[e], precision=jax.lax.Precision.HIGHEST)
        return jnp.sum(y * dy), y

    out = {"rows": rows, "group_sizes": sizes.tolist()}
    (gx, gw), y = jax.jit(jax.grad(fast, argnums=(0, 1), has_aux=True))(x, w)
    (rx, rw), ry = jax.jit(jax.grad(dense, argnums=(0, 1), has_aux=True))(x, w)
    for name, a, b in (("forward", y, ry), ("d_rows", gx, rx), ("d_weights", gw, rw)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        out[name] = float(np.max(np.abs(a - b)) / np.sqrt(np.mean(b[np.abs(b) > 0] ** 2)))
    return out


# ------------------------------------------------- the plain model, with the
# routing laid open: the same equations as chipbench/configs/lfm2_moe.py,
# from its own pieces; a routed layer's selection is handed in or handed out
def _routed(plain, model, p, h, mm, chosen=None):
    import jax
    import jax.numpy as jnp

    n_experts, k = int(model["num_experts"]), int(model["top_k"])
    held, offset = int(model["experts_held"]), int(model["expert_offset"])
    scores = jax.nn.sigmoid(jnp.matmul(h, p["router"], precision=jax.lax.Precision.HIGHEST))
    if chosen is None:
        _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores) + p["expert_bias"], k)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
    gates = (jax.nn.one_hot(chosen, n_experts) * weight[..., None]).sum(-2)
    out = jnp.zeros_like(h)
    for e in range(held):
        out = out + gates[..., offset + e, None] * plain._swiglu(
            p["w1"][e], p["w3"][e], p["w2"][e], h, mm
        )
    return out, chosen


def _block(plain, model, operator, ffn, p, x, mm, chosen=None):
    eps = float(model["norm_eps"])
    h = plain._rms_norm(x, p["op_norm"], eps)
    x = x + (plain._gated_conv if operator == "conv" else plain._attention)(model, p, h, mm)
    h = plain._rms_norm(x, p["ffn_norm"], eps)
    if ffn == "dense":
        return x + plain._swiglu(p["w1"], p["w3"], p["w2"], h, mm), None
    out, chosen = _routed(plain, model, p, h, mm, chosen)
    return x + out, chosen


def _head(model, params, x, mm, plain):
    x = plain._rms_norm(x, params[-3]["scale"], float(model["norm_eps"]))
    return mm(x[:, -1, :], params[-1]["kernel"]) + params[-1]["bias"]


def forward_open(plain, model, params, x, mm, selections=None):
    """→ (output, the selection of every routed layer). ``selections``, where
    given, are followed in place of the pass's own."""
    given = iter(selections or ())
    x = mm(x, params[0]["kernel"]) + params[0]["bias"]
    made = []
    for operator, ffn, p in zip(model["operators"], model["ffns"], params[1:-3]):
        chosen = next(given) if (selections and ffn == "routed") else None
        x, chosen = _block(plain, model, operator, ffn, p, x, mm, chosen)
        if ffn == "routed":
            made.append(chosen)
    return _head(model, params, x, mm, plain), made


def forced_reference(plain):
    """The plain model as a module whose ``forward`` computes with the
    caller's matmul under the selection a float32 pass over the same weights
    makes, layer by layer beside it (no gradient through that pass)."""
    import jax

    from chipbench import reference

    exact = reference.matmul("float32")

    def forward(model, params, x, mm):
        frozen = jax.lax.stop_gradient(params)
        shadow = exact(x, frozen[0]["kernel"]) + frozen[0]["bias"]
        x = mm(x, params[0]["kernel"]) + params[0]["bias"]
        layers = zip(model["operators"], model["ffns"], params[1:-3], frozen[1:-3])
        for operator, ffn, p, p_frozen in layers:
            def both(p, p_frozen, x, shadow, operator=operator, ffn=ffn):
                shadow, chosen = _block(plain, model, operator, ffn, p_frozen, shadow, exact)
                x, _ = _block(plain, model, operator, ffn, p, x, mm, chosen)
                return x, jax.lax.stop_gradient(shadow)

            x, shadow = jax.checkpoint(both)(p, p_frozen, x, shadow)
        return _head(model, params, x, mm, plain)

    module = types.ModuleType("chipbench.configs._forced_routing")
    module.init_params, module.forward = plain.init_params, forward
    module.forward_flops_per_window = plain.forward_flops_per_window
    sys.modules[module.__name__] = module
    return module.__name__.rsplit(".", 1)[1]


# --------------------------------------------------------------- the looks
def by_group(model: dict, observed_params, ref) -> dict:
    """The ``weights`` gap split by group of leaves, and the experts' expert
    by expert. Every leaf counts here (``check`` leaves out a leaf whose
    first gradient is round-off: the selection bias)."""
    groups = {k: [0.0, 0.0] for k in ("experts", "router", "rest")}
    experts = []
    for i, layer in enumerate(ref["init"]):
        routed = 1 <= i <= len(model["ffns"]) and model["ffns"][i - 1] == "routed"
        for key in sorted(layer):
            init = np.asarray(layer[key], np.float64)
            d_ref = np.asarray(ref["params"][i][key], np.float64) - init
            d_obs = np.asarray(observed_params[i][key], np.float64) - init
            name = "rest"
            if routed and key in ("w1", "w2", "w3"):
                name = "experts"
                axes = tuple(range(1, d_ref.ndim))
                experts.append({
                    "layer": i, "leaf": key,
                    "gap": np.sqrt(np.sum((d_obs - d_ref) ** 2, axis=axes)).tolist(),
                    "reference_change": np.sqrt(np.sum(d_ref**2, axis=axes)).tolist(),
                    "observed_change": np.sqrt(np.sum(d_obs**2, axis=axes)).tolist(),
                })
            elif routed and key == "router":
                name = "router"
            groups[name][0] += float(np.sum((d_obs - d_ref) ** 2))
            groups[name][1] += float(np.sum(d_ref**2))
    diff, base = (sum(g[j] for g in groups.values()) for j in (0, 1))
    return {
        "weights_all_leaves": float(np.sqrt(diff / base)),
        "groups": {
            name: {
                "gap": float(np.sqrt(g[0] / g[1])) if g[1] else None,
                "share_of_squared_gap": g[0] / diff,
                "share_of_squared_change": g[1] / base,
            }
            for name, g in groups.items()
        },
        "experts": experts,
    }


def by_window(observed_output, ref_output) -> list:
    gap = np.asarray(observed_output, np.float64) - np.asarray(ref_output, np.float64)
    scale = np.sqrt(np.mean(np.asarray(ref_output, np.float64) ** 2))
    return (np.sqrt(np.mean(gap**2, axis=-1)) / scale).tolist()


def flips(plain, config: dict, ref: dict, frame: np.ndarray, rows: int) -> dict:
    """Routing on the probe windows under the float32 reference's trained
    weights: float32 operands against bfloat16 operands."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference

    model = config["model"]
    lookback = int(model["lookback_window"])
    start, end = reference.probe_rows(rows, int(config["cv_splits"]))
    scaled = jnp.asarray(reference._minmax_scale(frame, rows))
    starts = jnp.arange(end - start - lookback + 1) + start
    x = reference._windows(scaled, starts, lookback)
    params = jax.tree_util.tree_map(jnp.asarray, ref["params"])
    held = set(range(int(model["expert_offset"]),
                     int(model["expert_offset"]) + int(model["experts_held"])))
    with jax.default_matmul_precision("highest"):
        run = jax.jit(
            lambda params, x, sel, mm: forward_open(plain, model, params, x, mm, sel),
            static_argnums=(3,),
        )
        exact, rounded = reference.matmul("float32"), reference.matmul("bfloat16")
        out32, sel32 = run(params, x, None, exact)
        out16, sel16 = run(params, x, None, rounded)
        out16_forced, _ = run(params, x, sel32, rounded)
    token_layers = differing = touching_held = last_position = 0
    for a, b in zip(sel32, sel16):
        a, b = np.sort(np.asarray(a), -1), np.sort(np.asarray(b), -1)
        token_layers += a.shape[0] * a.shape[1]
        moved = (a != b).any(-1)
        differing += int(moved.sum())
        for w, t in zip(*np.nonzero(moved)):
            if (set(a[w, t]) ^ set(b[w, t])) & held:
                touching_held += 1
                last_position += int(t == a.shape[1] - 1)
    return {
        "windows": int(x.shape[0]), "token_layers": token_layers,
        "selection_differs": differing, "touching_a_held_expert": touching_held,
        "of_those_at_the_last_position": last_position,
        "output_gap_own_routing": by_window(out16, out32),
        "output_gap_float32_routing": by_window(out16_forced, out32),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--forced", action="store_true")
    parser.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    from chipbench import check, reference
    from chipbench.run import Fleet, load_cell, observe_artifact, release_program, sample, set_up

    cell = load_cell(args.manifest, args.workload)
    config, tr = cell["config"], cell["traffic"]
    model = config["model"]
    devices = set_up(cell, args.rehearsal)
    plain = reference.model_reference(config)
    line = {"workload": args.workload, "seed": args.seed}

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def note(**kw):
        # a stage a line, written as it ends: a later stage that does not
        # fit takes nothing of the earlier ones with it
        with open(args.out, "a") as sink:
            sink.write(json.dumps(dict(line, **kw)) + "\n")
        print(json.dumps(kw)[:2000], file=sys.stderr, flush=True)

    tokens = int(model["batch_size"]) * int(model["lookback_window"])
    note(grouped_products=grouped_products_against_dense(model, tokens))

    out_root = tempfile.mkdtemp(prefix="moe-gap-look-")
    try:
        t0 = time.time()
        build = Fleet(cell, args.seed, out_root).build(tr.chunk_machines)
        names, paths, frames, probe = sample(cell, args.seed, [build])
        moved = {k: build["after"][k] - build["before"].get(k, 0.0)
                 for k in build["after"] if "moe" in k}
        observed = observe_artifact(paths[0], frames[0], probe)
        note(build_s=time.time() - t0, counters=moved)
    finally:
        shutil.rmtree(out_root, ignore_errors=True)
    release_program(devices)

    t0 = time.time()
    ref = reference.build_machines(config, names, frames, args.seed)[0]
    note(reference_s=time.time() - t0)

    def look(name, other):
        note(**{name: dict(
            numbers=check.gaps(other, ref, frames[0][probe]),
            windows=by_window(other["output"], ref["output"]),
            **by_group(model, other["params"], ref),
        )})

    look("program", observed)
    del observed
    note(flips=flips(plain, config, ref, frames[0], tr.rows))
    modes = [("bfloat16_reference", config)]
    if args.forced:
        forced = dict(config, reference=forced_reference(plain))
        modes.append(("bfloat16_reference_float32_routing", forced))
    for name, cfg in modes:
        t0 = time.time()
        other = reference.build_machines(cfg, names, frames, args.seed, precision="bfloat16")[0]
        note(**{name + "_s": time.time() - t0})
        look(name, check.as_observed(other, frames[0][probe]))
        del other
    return 0


if __name__ == "__main__":
    sys.exit(main())
