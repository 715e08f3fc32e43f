"""The one-hot table-gradient kernel (`ops/onehot_grad_pallas.py`) alone, on
the chip, against XLA's scatter-adds of the same tables: the 14 tables of at
most 4,096 rows of `benchmarks/configs/criteo_wdl.json` at its 2,865,039 rows,
by rows a grid step, with the largest gap between the two against the
largest gradient; then one table at a time at widths on both
sides of the cap, the kernel against its scatter-add, for the break-even.
One JSON line a reading on standard output. Inputs are drawn from a fixed
key. About three minutes on one v5e, from the root of a checkout:

    python3 scripts/onehot_grad_bench.py [rows]
"""

import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.getcwd())
from shifu_tpu.ops import onehot_grad_pallas as og  # noqa: E402

WIDTH = 9  # an embedding row of 8 and the wide weight


def emit(d):
    print(json.dumps(d), flush=True)


def data(n, vocab, seed=7):
    kc, kp = jax.random.split(jax.random.PRNGKey(seed))
    codes = jnp.stack([jax.random.randint(jax.random.fold_in(kc, i), (n,), 0,
                                          v) for i, v in enumerate(vocab)])
    planes = [jax.random.normal(jax.random.fold_in(kp, i), (WIDTH, n))
              for i in range(len(vocab))]
    return jax.block_until_ready((codes.astype(jnp.int32), planes))


def timed(fn, args, reps=6):
    """Median milliseconds of `reps` calls after a first (compiling) one."""
    out = jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t)
    return 1e3 * statistics.median(ts), out


def kernel_fn(vocab, interpret):
    og._build_call.cache_clear()
    return jax.jit(lambda c, p: og.table_grads(c, p, tuple(vocab), interpret))


def scatter_fn(vocab):
    return jax.jit(lambda c, p: tuple(
        jnp.zeros((v, WIDTH), jnp.float32).at[c[i]].add(p[i].T)
        for i, v in enumerate(vocab)))


def main():
    with open("benchmarks/configs/criteo_wdl.json") as f:
        cfg = json.load(f)
    n = int(sys.argv[1]) if len(sys.argv) > 1 else cfg["rows"]
    interpret = jax.default_backend() != "tpu"
    small = [cfg["vocab_sizes"][f]
             for f in og.small_fields(cfg["vocab_sizes"], WIDTH)]
    emit({"device": jax.devices()[0].device_kind, "rows": n,
          "tables": small})
    codes, planes = data(n, small)
    ms, ref = timed(scatter_fn(small), (codes, planes))
    emit({"what": "xla_scatter", "ms": ms})
    top = max(float(jnp.max(jnp.abs(r))) for r in ref)
    blk0 = og._BLK
    try:
        for blk in (512, 1024, 2048):
            og._BLK = blk
            try:
                ms, got = timed(kernel_fn(small, interpret), (codes, planes))
            except Exception as e:  # a shaping the compiler refuses
                emit({"what": "kernel", "blk": blk, "error": str(e)[:300]})
                continue
            gap = max(float(jnp.max(jnp.abs(a - b)))
                      for a, b in zip(got, ref))
            emit({"what": "kernel", "blk": blk, "ms": ms,
                  "us_a_step": 1e3 * ms / -(-n // blk),
                  "gap_of_largest": gap / top})
    finally:
        og._BLK = blk0
    for v in (128, 1024, 4096, 5653, 8192, 10001):
        codes, planes = data(n, [v], seed=v)
        s_ms, _ = timed(scatter_fn([v]), (codes, planes))
        try:
            k_ms, _ = timed(kernel_fn([v], interpret), (codes, planes))
        except Exception as e:  # past what the compiler takes
            emit({"what": "one_table", "rows": v, "scatter_ms": s_ms,
                  "error": str(e)[:300]})
            continue
        emit({"what": "one_table", "rows": v, "kernel_ms": k_ms,
              "scatter_ms": s_ms})


if __name__ == "__main__":
    main()
