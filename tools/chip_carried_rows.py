"""On the chip, at the published widths: a prefill chunk that carries a
decode step's rows against the two programs run one after the other, on
the same pools.  The benchmark's own comparison replays a request
through ``prefill_chunk`` with three arguments and ``step`` only, so it
cannot see the carried rows; this does, with the engine, weights and
settings of the benchmark's cell (chipbench's ``Served``).

    python3 tools/chip_carried_rows.py [--seed N] [--running 8]

Prints one JSON line: the largest relative error (max |a - b| / max |b|)
of the carried rows' logits against ``step``'s and against the plain
reference, of the chunk's last-row logits against the three-argument
call's, and whether the next tokens agree.  PERF.md holds the bar.
"""
import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = 'olmoe-1b-7b_serve_chat32_chunked'


def rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--seed', type=int, default=3000003100)
    ap.add_argument('--running', type=int, default=8)
    ap.add_argument('--rehearse', action='store_true',
                    help='the cell\'s toy shapes on the CPU')
    args = ap.parse_args()
    args.seconds, args.trace = 0.0, 0
    if args.rehearse:
        os.environ['JAX_PLATFORMS'] = 'cpu'
    from chipbench import harness
    from chipbench.kinds import serving
    from chipbench.reference import olmoe as reference
    from chipbench.systems import olmoe_serve
    import jax
    import jax.numpy as jnp
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    cell = next(w for w in bench['workloads'] if w['name'] == CELL)
    run = harness.Run(args, bench, cell)
    run.claim_device()
    served = olmoe_serve.Served(run, olmoe_serve.buckets_for(
        run.traffic['engine']['page_size'], serving.all_prompt_lengths(run)))
    eng, rng = served.engine, np.random.default_rng(args.seed)
    n = min(args.running, eng.max_streams)
    hi = eng.max_seq // 4
    vocab = run.config['vocab_size']

    def prefilled(length):
        prompt = rng.integers(1, vocab, length)
        pages = eng.cache.alloc(-(-(length + 1) // eng.page_size))
        for lo, up in eng.chunk_spans(length):
            logits = eng.prefill_chunk(prompt[lo:up], pages, lo)
        return prompt, pages, int(np.argmax(logits))

    toks = np.zeros(eng.max_streams, np.int32)
    ctx = np.zeros(eng.max_streams, np.int32)
    pt = np.full((eng.max_streams, eng.pages_per_stream), eng.cache.trash,
                 np.int32)
    slots = rng.permutation(eng.max_streams)[:n]
    seqs = {}
    for slot in slots:
        prompt, pages, tok = prefilled(int(rng.integers(hi // 8, hi)))
        pt[slot, :len(pages)] = pages
        toks[slot], ctx[slot] = tok, len(prompt)
        seqs[slot] = list(prompt) + [tok]
    chunk = rng.integers(1, vocab, eng.chunk_grid)
    mine = eng.cache.alloc(eng.chunk_grid // eng.page_size)
    # the same rows written three times with the same values: alone,
    # by the step, and by the chunk that carries the step
    last_alone = eng.prefill_chunk(chunk, mine, 0)
    nxt_step, logits_step = eng.step(toks, pt, ctx)
    last, nxt, logits = eng.prefill_chunk(chunk, mine, 0, toks, pt, ctx)
    # both calls leave the decode rows' logits on the device
    logits, logits_step = np.asarray(logits), np.asarray(logits_step)
    ref = jax.jit(lambda p, s: reference.logits(
        p, s, n_layers=served.layers, n_heads=served.heads))
    against_ref = []
    for slot in slots[:2]:
        seq = np.zeros(hi + eng.page_size, np.int32)
        seq[:len(seqs[slot])] = seqs[slot]
        want = np.asarray(ref(served.params, jnp.asarray(seq)))[
            len(seqs[slot]) - 1]
        against_ref.append(rel(logits[slot], want))
    print(json.dumps({
        'device': run.device, 'running': int(n),
        'context_lengths': sorted(int(c) for c in ctx[slots]),
        'carried_vs_step': rel(logits[slots], logits_step[slots]),
        'carried_vs_reference': against_ref,
        'step_vs_reference_tol': reference.LOGITS_TOL,
        'chunk_last_row_vs_alone': rel(last, last_alone),
        'next_tokens_equal': bool(np.array_equal(nxt[slots],
                                                 nxt_step[slots])),
        'compiles_after_warmup': eng.compiles_after_warmup}))


if __name__ == '__main__':
    main()
