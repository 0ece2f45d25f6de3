#!/usr/bin/env python3
"""Decode rows riding a prefill dispatch of the Mamba-2 hybrid
(`models/nemotron_h.forward(riders=)`), at PUBLISHED widths on the chip: what a
slot costs and whether a riding row's logits are its own.

    chiprun -- python3 scripts/nemotron_riders_check.py <out.json> [<seed>]

One runner (`nemotron3-nano-30b-ep8` at the cell's pools: 1,280 pages of 64, 32
seats) holds 16 rows with 512 tokens of context each and 4 rows that are
prefilled. Two parts:

  micro    median device-and-dispatch milliseconds of `prefill[B,512]` for B in
           1, 2, 4: alone (the slot-less program) | with an EMPTY slot of 16 |
           with 8 and with 16 live riders; and of `step1[8]`, `step1[16]`, the
           same rows' own decode step. (A rider's cost is the second and third
           column against the first; what it saves is `step1`.)
  logits   8 rows riding a 512-token chunk against the same 8 rows through
           `step1[8]`: largest |d logit| over rows x vocabulary and whether
           the greedy tokens agree, beside the same rows through `step1[16]`
           (8 live, 8 padded): the floor that a changed batch shape alone
           gives. Every arm starts from the same state: the rows' contexts are
           prefilled again before it (a chunk at position 0 starts from zero).

The benchmark's `correct` follows ONE request with nothing else in flight and
cannot see a rider (PERF.md section 7); this reading is the builder's own.
NEMOTRON_RIDERS_TOY=1 rehearses on the CPU with the toy preset.
"""
import json
import os
import statistics
import sys
import time

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from production_stack_tpu.engine.runner import ModelRunner, StepInput  # noqa: E402
from production_stack_tpu.engine.scheduler import Scheduler  # noqa: E402
from production_stack_tpu.models import nemotron_h as nh  # noqa: E402

TOY = bool(os.environ.get("NEMOTRON_RIDERS_TOY"))
PRESET = "nemotron-h-debug" if TOY else "nemotron3-nano-30b-ep8"
PAGE, CHUNK, WIDTH = (8, 16, 8) if TOY else (64, 512, 64)   # WIDTH: pages a table
POOL, SEATS = (256, 24) if TOY else (1280, 32)
R = Scheduler.RIDER_SLOTS
DECODING, CHUNKED = 16, 4          # rows that decode (seats 0-15), rows in prefill
PAGES_A_ROW = 2 * CHUNK // PAGE + 1
REPEATS = 3 if TOY else 12


def _sampling(n):
    return np.zeros(n, np.float32), np.zeros(n, np.int32), np.ones(n, np.float32)


def _table(rows, width):
    """Row i owns pages 1 + i * PAGES_A_ROW ... (page 0 heads the padding)."""
    table = np.zeros((len(rows), width), np.int32)
    for at, i in enumerate(rows):
        table[at, :PAGES_A_ROW] = 1 + i * PAGES_A_ROW + np.arange(PAGES_A_ROW)
    return table


def chunk(ids, rows, lo):
    """Positions lo .. lo + CHUNK of ``rows`` (their seats are their numbers)."""
    n = len(rows)
    return StepInput(
        ids[rows, lo:lo + CHUNK],
        np.tile(np.arange(lo, lo + CHUNK, dtype=np.int32), (n, 1)),
        _table(rows, WIDTH), np.full((n,), lo + CHUNK, np.int32), *_sampling(n),
        state_slots=np.asarray(rows, np.int32),
    )


def decode(ids, rows, B):
    """The next step of ``rows`` (CHUNK tokens of context each) in a batch of B."""
    n = len(rows)
    tok, pos = np.zeros((B, 1), np.int32), np.full((B, 1), -1, np.int32)
    lens, slots = np.zeros((B,), np.int32), np.full((B,), SEATS, np.int32)
    table = np.zeros((B, WIDTH), np.int32)
    tok[:n, 0], pos[:n], lens[:n], slots[:n] = ids[rows, CHUNK], CHUNK, CHUNK + 1, rows
    table[:n] = _table(rows, WIDTH)
    return StepInput(tok, pos, table, lens, *_sampling(B), state_slots=slots)


def riding(inp, ids, rows):
    """``inp`` with ``rows`` in its slot of R, the rest inert."""
    d = decode(ids, rows, R)
    inp.riders = (d.input_ids, d.positions, d.page_table, d.kv_lens,
                  d.temperature, d.top_k, d.top_p, d.state_slots)
    return inp


def main(out_path, seed=0):
    cfg = nh.PRESETS[PRESET]
    runner = ModelRunner(cfg, num_pages=POOL, page_size=PAGE, seed=seed,
                         state_slots=SEATS, max_batch=SEATS)
    assert runner.rider_refusal is None, runner.rider_refusal
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (DECODING + CHUNKED, 2 * CHUNK)).astype(np.int32)
    device = jax.devices()[0]
    out = {"device": f"{device.platform} {device.device_kind}", "preset": PRESET,
           "seed": seed, "rider_slots": R, "micro_ms": {}, "logits": {}}

    def contexts():
        """(Re)prefill the decoding rows' CHUNK tokens, four rows a dispatch."""
        for lo in range(0, DECODING, 4):
            runner.step(chunk(ids, list(range(lo, lo + 4)), 0))

    def timed(make):
        runner.step(make())   # the shape's first dispatch
        took = []
        for _ in range(REPEATS):
            inp = make()
            t0 = time.perf_counter()
            jax.block_until_ready(runner.step(inp))
            took.append(1e3 * (time.perf_counter() - t0))
        return round(statistics.median(took), 3)

    contexts()
    for B in (1, 2, 4):
        rows = list(range(DECODING, DECODING + B))
        name = f"prefill[{B},{CHUNK}]"
        out["micro_ms"][name] = timed(lambda: chunk(ids, rows, CHUNK))
        for live in (0, 8, 16):
            out["micro_ms"][f"{name}+R{R}live{live}"] = timed(
                lambda: riding(chunk(ids, rows, CHUNK), ids, list(range(live))))
        print(json.dumps(out["micro_ms"]), flush=True)
    for B in (8, 16):
        out["micro_ms"][f"step1[{B}]"] = timed(lambda: decode(ids, list(range(B)), B))

    def logits_of(step):
        contexts()
        got, logits = step()
        return np.asarray(got), np.asarray(logits, np.float32)

    eight = list(range(8))
    own_ids, own = logits_of(lambda: runner.step(decode(ids, eight, 8)))

    def against(logits, tokens):
        """Largest |d logit| a row (a router near-tie that rounding orders the
        other way shows as ONE row far from the others) and over all rows."""
        rows = np.abs(logits - own).max(axis=1)
        return {"max_abs_dlogit": float(rows.max()),
                "max_abs_dlogit_by_row": [round(float(x), 5) for x in rows],
                "greedy_tokens_agree": int((tokens == own_ids).sum())}

    wide_ids, wide = logits_of(lambda: runner.step(decode(ids, eight, 16)))
    for B in (1, 4):
        rows = list(range(DECODING, DECODING + B))
        runner.step(chunk(ids, rows, 0))
        rode_ids, rode = logits_of(lambda: runner.step(
            riding(chunk(ids, rows, CHUNK), ids, eight)))
        out["logits"][f"riding prefill[{B},{CHUNK}] vs step1[8]"] = against(
            rode[B:B + 8], rode_ids[B:B + 8])
    out["logits"]["step1[16] vs step1[8] (the floor)"] = against(wide[:8], wide_ids[:8])
    out["logits"]["rows"] = 8
    out["logits"]["logit_std"] = float(own.std())
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main(sys.argv[1], *map(int, sys.argv[2:3]))
