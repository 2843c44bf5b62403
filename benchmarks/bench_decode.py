"""seq2seq beam-search GENERATION throughput — the inference-side
counterpart of bench_seq2seq (reference book decode path: While-loop
beam lattice, layers.beam_search / beam_search_decode).

The decode program is one XLA While computation (the beam loop lowers
to a lax.scan).  K decodes ride ONE Executor.run_steps dispatch (the
predict_many treatment); a python loop of per-call dispatches times
the launches too.

Headline metric is GENERATED SEQUENCE tokens (batch x max_len) per
second — the conventional decode-throughput accounting.  The beam-
expanded rate (x beam_size hypotheses actually extended per step) is
reported as a secondary field, not the headline (r4 advisor item).

Prints ONE JSON line with the wall-vs-device split: device_ms_per_decode
comes from the K-chain (one dispatch amortized over K), and
dispatch_ms_per_call is the single-call residual over it.
"""
import json
import time

import numpy as np

from common import generated_tokens_per_sec, on_tpu


def main():
    import paddle_tpu as fluid
    from paddle_tpu.models import seq2seq

    if on_tpu():
        batch, seq, vocab, dim, beam, max_len = 64, 64, 30000, 512, 4, 32
        reps = 50
    else:
        batch, seq, vocab, dim, beam, max_len = 4, 8, 100, 32, 2, 5
        reps = 2

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        src = fluid.layers.data(name='src_word_id', shape=[1],
                                dtype='int64', lod_level=1)
        ids, scores = seq2seq.decode(
            src, vocab, word_dim=dim // 2, hidden_dim=dim,
            beam_size=beam, max_len=max_len)
    place = fluid.TPUPlace(0) if on_tpu() else fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(startup)

    rng = np.random.default_rng(0)
    ln = np.full((batch,), seq, np.int32)
    feed = {'src_word_id': (rng.integers(
        1, vocab, (batch, seq, 1)).astype(np.int32), ln)}

    # K decodes as one compiled scan, one dispatch, one sync
    out = exe.run_steps(main_p, feed=feed, fetch_list=[ids],
                        repeat=reps, return_numpy=False)  # compile+warm
    np.asarray(out[0])
    samples, walls = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        out = exe.run_steps(main_p, feed=feed, fetch_list=[ids],
                            repeat=reps, return_numpy=False)
        np.asarray(out[0])
        dt = time.perf_counter() - t0
        walls.append(dt)
        samples.append(generated_tokens_per_sec(
            batch * max_len * reps, dt))
    dev_ms = float(np.median(walls)) / reps * 1e3

    # single-call wall: the residual over the chained per-decode time
    # is per-dispatch cost
    out = exe.run(main_p, feed=feed, fetch_list=[ids],
                  return_numpy=False)
    np.asarray(out[0])
    t0 = time.perf_counter()
    out = exe.run(main_p, feed=feed, fetch_list=[ids],
                  return_numpy=False)
    np.asarray(out[0])
    single_ms = (time.perf_counter() - t0) * 1e3

    val = float(np.median(samples))
    print(json.dumps({
        'metric': 'seq2seq_beam_decode_tokens_per_sec',
        'value': round(val, 2),
        'samples': [round(s, 1) for s in samples],
        'beam_expanded_tokens_per_sec': round(val * beam, 1),
        'device_ms_per_decode': round(dev_ms, 2),
        'dispatch_ms_per_call': round(max(single_ms - dev_ms, 0.0), 2),
        'chain': reps,
        'note': 'batch=%d beam=%d max_len=%d vocab=%d dim=%d; headline '
                'counts batch*max_len generated tokens via '
                'common.generated_tokens_per_sec — the same accounting '
                'as bench_serving decode (beam-expanded rate is the '
                'secondary field)'
                % (batch, beam, max_len, vocab, dim)}))


if __name__ == '__main__':
    main()
