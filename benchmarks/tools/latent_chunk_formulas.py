"""The two formulas of a prompt chunk's latent attention, timed alone on
the chip at a serving cell's own shapes (ISSUE 34 asks for both to be
measured and the faster kept):

    python3 benchmarks/tools/latent_chunk_formulas.py --workload W [--blocks 4,8,16]

`ops/paged_attention.py` `_latent_chunk_attend` with `absorbed` True and
False, at each of `--blocks` heads a step, over a seeded pool of the
cell's size and the table of its longest prompt; then the decode step's
`_latent_attend` at every slot live with half and with all of its
context.  One JSON line a reading (median of `--reps` calls, each waited
for), appended to chiprun_out/latent_formulas_<workload>.jsonl.
"""
import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def timed(fn, reps):
    fn().block_until_ready()          # compile and warm
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn().block_until_ready()
        out.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(out), min(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--blocks", default="4,8,16")
    ap.add_argument("--reps", type=int, default=10)
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from benchmarks import harness
    from benchmarks.flops import mla_moe
    from bigdl_tpu.ops import paged_attention_mod as pa
    cell = harness.Cell(a.workload)
    harness.find_devices(cell.chips)
    cfg, eng = cell.config, cell.traffic["engine"]
    m = mla_moe.dims(cfg)
    dt = jnp.dtype(cfg["activation_dtype"])
    page, slots = eng["page_size"], eng["slots"]
    per_slot = -(-eng["max_context"] // page)
    chunk = eng["prefill_chunk"]
    n_table = -(-eng["max_prompt"] // chunk) * chunk // page
    width = m["rank"] + m["rope"]
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    pool = jax.random.normal(ks[0], (slots * per_slot, page, width), dt)
    q = jax.random.normal(ks[1], (m["h"], chunk, m["nope"] + m["rope"]), dt)
    w_uk = jax.random.normal(ks[2], (m["rank"], m["h"], m["nope"]), dt) * 0.04
    w_uv = jax.random.normal(ks[3], (m["rank"], m["h"], m["v"]), dt) * 0.04
    table = jnp.arange(n_table, dtype=jnp.int32)
    scale = float((m["nope"] + m["rope"]) ** -0.5)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out",
                            f"latent_formulas_{a.workload}.jsonl"), "a")

    def emit(row):
        print(json.dumps(row), flush=True)
        log.write(json.dumps(row) + "\n")
        log.flush()

    start = jnp.int32(n_table * page - chunk)
    flops = mla_moe.chunk_attention_flops(cfg, n_table * page,
                                          chunk * n_table * page)
    for hb in (int(x) for x in a.blocks.split(",")):
        pa._LATENT_HEAD_BLOCK = hb
        for absorbed in (True, False):
            pa._latent_chunk_attend.clear_cache()
            med, best = timed(lambda: pa._latent_chunk_attend(
                q, w_uk, w_uv, pool, table, start, rank=m["rank"],
                sm_scale=scale, absorbed=absorbed), a.reps)
            emit({"piece": "chunk", "absorbed": absorbed, "head_block": hb,
                  "keys": n_table * page, "ms_median": med, "ms_min": best,
                  "up_projected_tflop": flops / 1e12})
    qd = jax.random.normal(ks[4], (slots, m["h"], width), dt)
    tables = jnp.arange(slots * per_slot, dtype=jnp.int32).reshape(
        slots, per_slot)
    for share in (0.5, 1.0):
        lengths = jnp.full((slots,), int(share * per_slot * page) - 1,
                           jnp.int32)
        med, best = timed(lambda: pa._latent_attend(
            qd, pool, tables, lengths, rank=m["rank"], sm_scale=scale),
            a.reps)
        emit({"piece": "decode", "slots": slots,
              "rows_live": int((lengths + 1).sum()), "ms_median": med,
              "ms_min": best, "row_bytes": width * dt.itemsize})


if __name__ == "__main__":
    main()
