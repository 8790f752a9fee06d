"""Real-chip validation + microbench for the device hot path's Pallas
kernels.

The validation gate for every hand-written kernel: on a live chip it
judges each kernel set against its contract and prints one JSON line per
geometry and one per set:

- ``scan`` — ``reverse_linear_scan_pallas`` + its explicit-DMA twin
  (``pallas_dma`` — the ROADMAP item-2 beachhead whose start/wait
  discipline the PAL static pass guards) vs the ``lax.associative_scan``
  reference, judged against a float64 sequential truth (scale-aware
  RMS-relative error — a per-element relative metric falsely flags
  rounding tails at large T*B; see the inline comment).
- ``fused`` — the fused V-trace/GAE tail kernel (``ops/pallas_scan.py``)
  vs the sequential lax reference: the contract is BIT-identity (all
  four V-trace outputs and both GAE outputs, ``np.array_equal``), the
  same claim tests/test_differential.py pins through the interpreter,
  here on real silicon where the Mosaic compiler (not the interpreter)
  decides FMA contraction.

    python scripts/validate_pallas_tpu.py [scan] [fused]

No argv = all sets. Exit 0 = every selected set matched (safe to
promote); exit 1 = mismatch (keep the lax defaults; the geometry's line
says which); exit 2 = no accelerator / bad argv.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import functools

from asyncrl_tpu.ops.scan import reverse_linear_scan

# (T, B): preset fragment shapes (unroll_len x num_envs) plus a long-horizon
# sequence-parallel shape (SURVEY.md §5.7) and a ragged-tile edge case.
GEOMETRIES = [(32, 256), (32, 1024), (16, 64), (128, 4096), (20, 96)]


def timed(fn, *args, reps=20):
    jax.block_until_ready(fn(*args))  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def validate_scan() -> bool:
    rng = np.random.default_rng(0)
    results = []
    ok = True
    for T, B in GEOMETRIES:
        a = jnp.asarray(rng.uniform(0.8, 1.0, (T, B)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(T, B)).astype(np.float32))
        ref_fn = jax.jit(
            functools.partial(reverse_linear_scan, impl="associative")
        )
        pal_fn = jax.jit(
            functools.partial(reverse_linear_scan, impl="pallas")
        )
        dma_fn = jax.jit(
            functools.partial(reverse_linear_scan, impl="pallas_dma")
        )
        ref = jax.device_get(ref_fn(a, b))
        outs = {}
        errors = {}
        for name, fn in (("pallas", pal_fn), ("pallas_dma", dma_fn)):
            try:
                outs[name] = jax.device_get(fn(a, b))
            except Exception as e:  # noqa: BLE001 — record, don't crash
                errors[name] = str(e)[:300]
        if errors and not outs:
            results.append({"T": T, "B": B, "error": errors})
            ok = False
            continue
        # Judge every f32 implementation against a float64 sequential
        # truth, scale-aware (max abs error over the fragment's RMS).
        # A per-element relative metric is unusable here: b is zero-mean,
        # so some (t, col) entries cancel to near zero and the max over
        # T*B samples of |d|/|ref| reads as "mismatch" purely from f32
        # rounding tails — measured 0.013 between two CORRECT f32 impls
        # on CPU at (128, 4096) while the scale-aware error was ~1e-6.
        xs = np.zeros(B, np.float64)
        truth = np.zeros((T, B), np.float64)
        a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)
        for t in range(T - 1, -1, -1):
            xs = b64[t] + a64[t] * xs
            truth[t] = xs
        rms = float(np.sqrt(np.mean(truth**2))) or 1.0
        err_ref = float(np.max(np.abs(ref - truth))) / rms
        entry = {
            "T": T, "B": B,
            "rms_rel_err_associative": err_ref,
            "associative_us": round(timed(ref_fn, a, b) * 1e6, 1),
        }
        if errors:
            entry["error"] = errors
        match = not errors
        for name, fn in (("pallas", pal_fn), ("pallas_dma", dma_fn)):
            if name not in outs:
                continue
            err = float(np.max(np.abs(outs[name] - truth))) / rms
            # A kernel passes if it is no worse than the associative tree
            # (2x margin for fma-ordering differences) AND under an
            # absolute scale-aware ceiling: the relative gate alone would
            # stamp ok:true in a regime where BOTH f32 implementations
            # are badly wrong (shared-error blind spot — ADVICE r3). 1e-3
            # is ~100x the worst healthy f32 error observed across the
            # swept geometries.
            kernel_ok = bool(
                err <= max(2.0 * err_ref, 1e-5) and err < 1e-3
            )
            match = match and kernel_ok
            t_k = timed(fn, a, b)
            entry[f"rms_rel_err_{name}"] = err
            entry[f"{name}_us"] = round(t_k * 1e6, 1)
            entry[f"{name}_speedup"] = round(
                entry["associative_us"] / max(t_k * 1e6, 1e-9), 2
            )
        # Back-compat aliases for older tooling.
        if "rms_rel_err_pallas" in entry:
            entry["rms_rel_err"] = entry["rms_rel_err_pallas"]
            entry["speedup"] = entry["pallas_speedup"]
        entry["match"] = match
        ok = ok and match
        results.append(entry)
        print(json.dumps(entry))

    print(json.dumps({"kernel": "scan", "ok": ok, "n": len(results)}))
    return ok


def validate_fused() -> bool:
    """Fused V-trace/GAE vs the sequential lax reference: bit-identity,
    on the real Mosaic-compiled kernel."""
    from asyncrl_tpu.ops.gae import gae
    from asyncrl_tpu.ops.vtrace import vtrace

    rng = np.random.default_rng(1)
    results = []
    ok = True
    for T, B in GEOMETRIES:
        f = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
        kw = dict(
            behaviour_logp=f(T, B), target_logp=f(T, B), rewards=f(T, B),
            discounts=jnp.asarray(
                (0.99 * (rng.random((T, B)) > 0.1)).astype(np.float32)
            ),
            values=f(T, B), bootstrap_value=f(B),
        )
        vt_ref = jax.jit(
            functools.partial(vtrace, scan_impl="sequential", fused="lax")
        )
        vt_pal = jax.jit(functools.partial(vtrace, fused="pallas"))
        entry = {"T": T, "B": B}
        try:
            ref = jax.device_get(vt_ref(**kw))
            out = jax.device_get(vt_pal(**kw))
        except Exception as e:  # noqa: BLE001 — record, don't crash
            entry["error"] = str(e)[:300]
            entry["match"] = False
            ok = False
            results.append(entry)
            print(json.dumps(entry))
            continue
        match = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(ref, out)
        )
        mismatched = [
            name for name, a, b in zip(ref._fields, ref, out)
            if not np.array_equal(np.asarray(a), np.asarray(b))
        ]
        g_ref = jax.device_get(gae(
            kw["rewards"], kw["discounts"], kw["values"],
            kw["bootstrap_value"], gae_lambda=0.95,
            scan_impl="sequential", fused="lax",
        ))
        g_out = jax.device_get(gae(
            kw["rewards"], kw["discounts"], kw["values"],
            kw["bootstrap_value"], gae_lambda=0.95, fused="pallas",
        ))
        if not all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(g_ref, g_out)
        ):
            match = False
            mismatched.append("gae")
        t_ref = timed(lambda: vt_ref(**kw))
        t_pal = timed(lambda: vt_pal(**kw))
        entry.update({
            "match": match,
            "lax_us": round(t_ref * 1e6, 1),
            "pallas_us": round(t_pal * 1e6, 1),
            "speedup": round(t_ref / max(t_pal, 1e-9), 2),
        })
        if mismatched:
            entry["mismatched"] = mismatched
        ok = ok and match
        results.append(entry)
        print(json.dumps(entry))

    print(json.dumps({"kernel": "fused", "ok": ok, "n": len(results)}))
    return ok


KERNEL_SETS = {
    "scan": validate_scan,
    "fused": validate_fused,
}


def main() -> int:
    selected = sys.argv[1:] or list(KERNEL_SETS)
    unknown = [k for k in selected if k not in KERNEL_SETS]
    if unknown:
        print(
            f"validate_pallas_tpu: unknown kernel set(s) {unknown}; "
            f"expected any of {list(KERNEL_SETS)}",
            file=sys.stderr,
        )
        return 2
    if jax.devices()[0].platform == "cpu":
        print("validate_pallas_tpu: no accelerator; refusing (the whole "
              "point is real-chip behaviour)", file=sys.stderr)
        return 2
    from asyncrl_tpu.utils import runtime

    runtime.enable_compile_cache()
    ok = True
    for name in selected:
        ok = KERNEL_SETS[name]() and ok
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
