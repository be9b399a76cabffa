"""Abstract-topology AOT compile matrix for the unified GSPMD train step.

ISSUE 12: pod-scale correctness must be CI-testable on a CPU box.  This
tool forces a large virtual CPU device count in ONE fresh subprocess,
carves sub-meshes for each requested ``(batch, model)`` topology —
(1,1) one chip, (8,1) a v5e-8 host, (16,4)/(64,4) v5e-64/-256 pod
slices — and for each:

* builds the runner's program for the tiny probe model's flags on that
  mesh (``runners/train.py:build_program`` → ``build_steps``: the step is
  the one ``main`` trains with, not a copy), a TrainState and the
  sharding-rule table (``parallel/sharding.py:train_state_shardings``);
* AOT-lowers and compiles that unified ``jax.jit`` train step against
  abstract ``ShapeDtypeStruct`` inputs carrying the table's
  ``NamedSharding`` annotations;
* asserts, from the compiled executable, that every TrainState leaf's
  input AND output sharding matches the table (the GSPMD program honors
  the annotations at every topology) and that state donation survived
  (``input_output_alias`` in the post-optimization HLO);
* records lowering / compile wall-time and HLO size per topology.

The verdict is printed (``--out FILE`` also writes it as JSON).
``tests/test_mesh_aot.py`` runs the same child with the acceptance shapes;
the verify recipe runs ``--smoke``.

Usage::

    python tools/bench_multichip.py                  # full default matrix
    python tools/bench_multichip.py --smoke          # (1,1),(8,1) only
    python tools/bench_multichip.py --shapes 64x4    # one topology
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_SHAPES = ((1, 1), (8, 1), (16, 4), (64, 4))
SMOKE_SHAPES = ((1, 1), (8, 1))


def parse_shapes(spec: str):
    out = []
    for part in spec.split(","):
        b, _, m = part.strip().partition("x")
        out.append((int(b), int(m or "1")))
    return tuple(out)


# ---------------------------------------------------------------------------
# child: devices already forced — run the matrix and print one JSON line
# ---------------------------------------------------------------------------

def run_matrix(shapes, model_name: str, size: int, batch_per_dp: int,
               log=lambda m: print(m, file=sys.stderr, flush=True)):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from deepfake_detection_tpu.config import TrainConfig
    from deepfake_detection_tpu.models import init_model
    from deepfake_detection_tpu.parallel import (batch_sharding,
                                                 make_train_mesh,
                                                 replicated_sharding,
                                                 train_state_shardings)
    from deepfake_detection_tpu.runners import train as T
    from deepfake_detection_tpu.train import create_train_state

    n_needed = max(b * m for b, m in shapes)
    devs = jax.devices()
    if len(devs) < n_needed:
        raise SystemExit(
            f"need {n_needed} devices, have {len(devs)} — run through the "
            "parent mode (it forces the virtual device count)")

    # the step is the runner's for these flags, on each topology's mesh
    cfg = TrainConfig.from_args([
        "--model", model_name, "--model-version", "", "--dataset",
        "synthetic", "--input-size-v2", f"3,{size},{size}", "-b",
        str(batch_per_dp), "--opt", "sgd", "--lr", "1e-3", "--drop", "0.0",
        "--compute-dtype", "float32"])
    state = None

    # production-default rows (replicated params) for every topology, plus
    # ONE fsdp row on the first multi-device shape: without it every
    # expected spec is P() and the "leaf keeps its PartitionSpec"
    # assertion would be vacuous — the fsdp row makes it bite on real
    # non-trivial shardings (moments/EMA following their params included)
    jobs = [(b, m, False) for b, m in shapes]
    multi = next(((b, m) for b, m in shapes if b > 1), None)
    if multi is not None:
        jobs.append((multi[0], multi[1], True))

    rows = []
    for b_ax, m_ax, fsdp in jobs:
        n = b_ax * m_ax
        mesh = make_train_mesh(batch=b_ax, model=m_ax,
                               devices=devs[:n])
        program = T.build_program(dataclasses.replace(cfg, fsdp=fsdp),
                                  mesh=mesh)
        if state is None:
            # donate=False: the SAME eager state seeds every topology's
            # table (each program's optimizer has the one structure)
            variables = init_model(program.model, jax.random.PRNGKey(0),
                                   (2, size, size, 3), training=True)
            state = create_train_state(variables, program.tx, donate=False)
            n_params = sum(x.size for x in jax.tree.leaves(state.params))
        shardings = train_state_shardings(state, mesh, fsdp=fsdp,
                                          axis=program.batch_axis)
        batch_sh = batch_sharding(mesh)
        rep = replicated_sharding(mesh)
        step, _, _ = T.build_steps(program, shardings)
        st_abs = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            state, shardings)
        B = program.global_batch
        x_abs = jax.ShapeDtypeStruct((B, size, size, 3), jnp.float32,
                                     sharding=batch_sh)
        y_abs = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=batch_sh)
        key = jax.random.PRNGKey(0)
        r_abs = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep)

        log(f"mesh ({b_ax},{m_ax}){' fsdp' if fsdp else ''}: lowering ...")
        t0 = time.monotonic()
        lowered = step.lower(st_abs, x_abs, y_abs, r_abs)
        t1 = time.monotonic()
        exe = lowered.compile()
        t2 = time.monotonic()
        hlo = exe.as_text()

        # --- assertions the test tier relies on -------------------------
        flat_expected = jax.tree.leaves(shardings)
        # input_shardings[0] is the per-ARG tuple (state is argument 0);
        # output_shardings is the (state, metrics) output pytree — take
        # the state pytree of each and compare leaf-for-leaf
        in_state = jax.tree.leaves(exe.input_shardings[0][0])
        out_state = jax.tree.leaves(exe.output_shardings[0])
        # a silent zip truncation would let specs_ok pass with leaves
        # unverified if a jax upgrade changes the executable's sharding
        # representation — demand exact leaf-count agreement first
        if not (len(in_state) == len(out_state) == len(flat_expected)):
            raise AssertionError(
                f"sharding leaf-count mismatch: table {len(flat_expected)} "
                f"vs executable in {len(in_state)} / out {len(out_state)}")
        spec_misses = []
        for i, (want, got_in, got_out) in enumerate(
                zip(flat_expected, in_state, out_state)):
            if got_in.spec != want.spec or got_out.spec != want.spec:
                spec_misses.append((i, str(want.spec), str(got_in.spec),
                                    str(got_out.spec)))
        donation = "input_output_alias" in hlo
        from jax.sharding import PartitionSpec as _P
        sharded_leaves = sum(1 for s in flat_expected if s.spec != _P())
        rows.append({
            "mesh_shape": [b_ax, m_ax],
            "axis_names": list(mesh.axis_names),
            "fsdp": fsdp,
            "sharded_leaves": sharded_leaves,
            "n_devices": n,
            "global_batch": B,
            "model": model_name,
            "image_size": size,
            "n_params": int(n_params),
            "lower_s": round(t1 - t0, 3),
            "compile_s": round(t2 - t1, 3),
            "hlo_bytes": len(hlo),
            "state_leaves": len(flat_expected),
            "specs_ok": not spec_misses,
            "spec_misses": spec_misses[:8],
            "donation_preserved": donation,
        })
        log(f"mesh ({b_ax},{m_ax}): lower {t1-t0:.1f}s "
            f"compile {t2-t1:.1f}s hlo {len(hlo)}B "
            f"specs_ok={not spec_misses} donation={donation}")
    return {
        "kind": "abstract_mesh_aot",
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "rows": rows,
        "ok": all(r["specs_ok"] and r["donation_preserved"] for r in rows),
    }


def child_main(args) -> int:
    from deepfake_detection_tpu.utils.compile_cache import \
        setup_compile_cache
    setup_compile_cache()
    doc = run_matrix(parse_shapes(args.shapes), args.model, args.size,
                     args.batch_per_dp)
    print(json.dumps(doc), flush=True)
    return 0 if doc["ok"] else 1


# ---------------------------------------------------------------------------
# parent: fresh interpreter with the forced virtual device count
# ---------------------------------------------------------------------------

def parent_main(args) -> int:
    shapes = parse_shapes(args.shapes)
    n_needed = max(b * m for b, m in shapes)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={n_needed}"
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--shapes", args.shapes, "--model", args.model,
           "--size", str(args.size),
           "--batch-per-dp", str(args.batch_per_dp)]
    r = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                       text=True, timeout=args.timeout)
    sys.stderr.write(r.stderr[-4000:])
    if r.returncode != 0 and not r.stdout.strip():
        print(f"child failed rc={r.returncode}", file=sys.stderr)
        return r.returncode or 1
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    doc["host"] = os.uname().nodename
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
    # the child's stderr, relayed above, has a line for each row
    print(f"ok={doc['ok']}" + (f", wrote {args.out}" if args.out else ""))
    return 0 if doc["ok"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--shapes", default=None,
                    help="comma list of BxM topologies "
                         "(default: 1x1,8x1,16x4,64x4)")
    ap.add_argument("--smoke", action="store_true",
                    help="just (1,1),(8,1) — the verify-recipe smoke")
    ap.add_argument("--model", default="mnasnet_small",
                    help="probe model (tiny by design: the sharding table "
                         "and step program are model-size independent)")
    ap.add_argument("--size", type=int, default=32)
    ap.add_argument("--batch-per-dp", type=int, default=2)
    ap.add_argument("--timeout", type=int, default=480)
    ap.add_argument("--out", default=None,
                    help="also write the JSON verdict to this file")
    ap.add_argument("--child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.shapes is None:
        args.shapes = ",".join(
            f"{b}x{m}" for b, m in
            (SMOKE_SHAPES if args.smoke else DEFAULT_SHAPES))
    return child_main(args) if args.child else parent_main(args)


if __name__ == "__main__":
    sys.exit(main())
