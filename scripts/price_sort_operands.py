"""Price one `lax.sort` operand on the chip, in seconds, without a
whole-query compile: ms by lanes x operand form.

    chiprun -- python scripts/price_sort_operands.py
    JAX_PLATFORMS=cpu python scripts/price_sort_operands.py --rehearse

Every sort of the served programs (ops/sortjoin.py, ops/agg.py,
coldata/batch.first_matches, parallel/repartition.py) is one
of the forms below. Each form is ONE jitted sort compiled with the fused
runner's own options (exec/fused.TPU_COMPILE_OPTIONS), run `--reps` times
after a warm-up, timed on the host clock around `block_until_ready`
(the median; a sort of millions of lanes is 2-35 ms, a dispatch 0.1).
A sorting network's time does not depend on the data, so the inputs are
the shapes of the join's (duplicate keys, a value that is two ranges
end to end), made from `--seed`.

The question it was written for (PR 43): does the default
`is_stable=True` cost an operand (XLA's iota tie-break) that
`is_stable=False` over a total key saves? Read a form's `stable` column
against its `unstable` ones.

It refuses to price anything on a CPU (`--rehearse` runs tiny shapes
there to test the script, and says so on every line). One JSON line a
form, then the table; the lines also go to
chiprun_out/price_sort_operands.jsonl.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

import cockroach_tpu  # noqa: E402,F401 (x64 on, the compile cache placed)

# the lanes the cells' programs sort at SF1 (PERF.md section 4): the
# lineitem joins, the int-key aggregate and the standing Shrink, Q9's
# orders join, the semi joins, the mesh's routed sides
JOIN_LANES = (8_650_752, 8_388_608, 2_621_440, 2_228_224)
# the forms with a 64-bit operand are priced where the cells run them: the
# int-key aggregate, and Q9's resorting orders join
WIDE_LANES = (8_388_608, 2_621_440)
ROUTER_LANES = 2_097_152
ROUTER_COLUMNS = 7


def _sort(num_keys, stable):
    return lambda *ops: lax.sort(ops, num_keys=num_keys, is_stable=stable)


def _argsort_stable(sel, _lane):
    return jnp.argsort(~sel, stable=True)


def _one_u32(sel, lane):
    top = np.uint32(1 << 31)
    return lax.sort(jnp.where(sel, lane, lane | top), is_stable=False)


def _router_packed(dest, *lanes):
    n = dest.shape[0]
    key = dest.astype(jnp.uint32) * np.uint32(n) + jnp.arange(
        n, dtype=jnp.uint32)
    return lax.sort((key, *lanes), num_keys=1, is_stable=False)


def forms(n, rng):
    """-> [(form, column, fn, operands)] at n lanes. `form` is what is
    sorted, `column` how: the table prints one row a (lanes, form)."""
    build = n // 32
    key = (rng.integers(0, 1 << 20, n, dtype=np.uint32) << 1) | np.concatenate(
        [np.zeros(build, np.uint32), np.ones(n - build, np.uint32)])
    val = np.concatenate([np.arange(build, dtype=np.uint32),
                          np.arange(n - build, dtype=np.uint32)])
    val64 = rng.integers(0, 1 << 62, n, dtype=np.uint64)
    key64 = rng.integers(0, 1 << 63, n, dtype=np.uint64)
    iota = np.arange(n, dtype=np.int32)
    perm = rng.permutation(n).astype(np.int32)
    sel = rng.random(n) < 0.05
    wide = n in WIDE_LANES
    out = [
        # the joins' key sort: (packed key, row-or-lane index)
        ("u32,u32", "stable k1", _sort(1, True), (key, val)),
        ("u32,u32", "unstable k1", _sort(1, False), (key, val)),
        ("u32,u32", "unstable k2", _sort(2, False), (key, val)),
        # the Shrink: argsort(~sel, stable) against first_matches
        ("pred,iota", "stable k1", _argsort_stable, (sel, iota)),
        ("pred,iota", "unstable k2", _sort(2, False), (~sel, iota)),
        ("u32", "unstable k1", _one_u32, (sel, iota.astype(np.uint32))),
    ]
    if not wide:
        return out
    return out + [
        # the resorting join's key sort and the int-key aggregate's sort:
        # a u64 payload beside the key
        ("u32,u64", "stable k1", _sort(1, True), (key, val64)),
        ("u32,u64", "unstable k1", _sort(1, False), (key, val64)),
        # the resort by destination (a permutation) with its u64 payload
        ("s32perm,u64", "stable k1", _sort(1, True), (perm, val64)),
        ("s32perm,u64", "unstable k1", _sort(1, False), (perm, val64)),
        # probe_unique's hashed key and the hash aggregate: (u64, iota)
        ("u64,iota", "stable k1", _sort(1, True), (key64, iota)),
        ("u64,iota", "unstable k2", _sort(2, False), (key64, iota)),
    ]


def router_forms(n, rng):
    dest = rng.integers(0, 5, n, dtype=np.int32)
    cols = tuple(rng.integers(0, 1 << 32, n, dtype=np.uint32)
                 for _ in range(ROUTER_COLUMNS))
    return [
        (f"s32,{ROUTER_COLUMNS}xu32", "stable k1", _sort(1, True),
         (dest, *cols)),
        (f"s32,{ROUTER_COLUMNS}xu32", "unstable k1 packed",
         _router_packed, (dest, *cols)),
    ]


def price(fn, operands, reps, options):
    args = [jax.device_put(o) for o in operands]
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile(options)
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times), min(times), compile_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=43)
    ap.add_argument("--reps", type=int, default=15)
    ap.add_argument("--lanes", type=str, default="",
                    help="comma list; default: the cells' lanes at SF1")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on whatever backend there is: "
                         "tests the script, prices nothing")
    a = ap.parse_args()

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not a.rehearse:
        sys.exit(f"price_sort_operands: {dev.platform} is no chip; a sort's "
                 f"price comes only from one (--rehearse tests the script)")
    from cockroach_tpu.exec.fused import TPU_COMPILE_OPTIONS

    options = TPU_COMPILE_OPTIONS if on_tpu else None
    lanes = ([int(x) for x in a.lanes.split(",")] if a.lanes
             else ([4096, 2048] if a.rehearse else list(JOIN_LANES)))
    if a.rehearse:
        global WIDE_LANES
        WIDE_LANES = (4096,)
    router = 2048 if a.rehearse else ROUTER_LANES
    rng = np.random.default_rng(a.seed)

    os.makedirs("chiprun_out", exist_ok=True)
    rows = []
    with open("chiprun_out/price_sort_operands.jsonl", "w") as out:
        for n, make in [(n, forms) for n in lanes] + [(router, router_forms)]:
            for form, column, fn, operands in make(n, rng):
                ms, best, compile_s = price(fn, operands, a.reps, options)
                row = {"lanes": n, "form": form, "how": column,
                       "ms_median": round(ms, 3), "ms_min": round(best, 3),
                       "compile_s": round(compile_s, 1), "reps": a.reps,
                       "device": dev.device_kind, "platform": dev.platform,
                       "priced": on_tpu}
                rows.append(row)
                line = json.dumps(row)
                print(line, flush=True)
                out.write(line + "\n")
                out.flush()

    hows = []
    for r in rows:
        if r["how"] not in hows:
            hows.append(r["how"])
    unit = "ms" if on_tpu else "ms on a CPU: NOT a price"
    print(f"\n{dev.device_kind} ({dev.platform}), median of {a.reps}, {unit}")
    print("| lanes | operands | " + " | ".join(hows) + " |")
    print("|---|---|" + "---|" * len(hows))
    seen = []
    for r in rows:
        k = (r["lanes"], r["form"])
        if k in seen:
            continue
        seen.append(k)
        cells = {q["how"]: q["ms_median"] for q in rows
                 if (q["lanes"], q["form"]) == k}
        print(f"| {k[0]:,} | ({k[1]}) | " + " | ".join(
            f"{cells[h]:.2f}" if h in cells else "" for h in hows) + " |")


if __name__ == "__main__":
    main()
