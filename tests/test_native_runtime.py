"""N1-N3/A4 — native runtime tests: queue order/termination/concurrency,
recordio round-trip + cross-compat with the python format, staging arena
reuse, prefetch/xmap pipelines.

Reference parity: the reference's threadpool tests
(paddle/framework/threadpool_test.cc) and recordio round-trips.
"""
import os
import threading

import numpy as np
import pytest

from paddle_tpu.runtime import (available, NativeQueue, NativeRecordReader,
                                NativeRecordWriter, StagingArena,
                                prefetch_reader, xmap_native)
from paddle_tpu import io_recordio


def test_native_library_builds():
    # g++ is in the image: the C++ path must actually be exercised by CI
    assert available(), "native runtime failed to build/load"


def test_build_is_named_by_source_hash_and_fails_loudly(tmp_path,
                                                        monkeypatch):
    """A copied tree carries no meaningful mtimes: the library is named
    by the source's hash, and a build that fails raises with the
    compiler's stderr instead of degrading to the pure-Python path;
    PADDLE_TPU_USE_NATIVE_RUNTIME=0 is the stated way to run without."""
    import hashlib

    from paddle_tpu.runtime import native
    with open(native._src, 'rb') as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    assert digest in os.path.basename(native._so_path())

    bad = tmp_path / 'broken.cc'
    bad.write_text('this is not C++\n')
    monkeypatch.setattr(native, '_src', str(bad))
    monkeypatch.setattr(native, '_build_dir', str(tmp_path / 'build'))
    monkeypatch.setattr(native, '_lib', None)
    with pytest.raises(RuntimeError, match='(?s)g\\+\\+.*error'):
        native._load()
    monkeypatch.setenv('PADDLE_TPU_USE_NATIVE_RUNTIME', '0')
    assert native._load() is None and not native.available()
    assert not NativeQueue(capacity=2).native


def test_queue_fifo_order_and_close():
    q = NativeQueue(capacity=4)
    assert q.native == available()
    for i in range(4):
        assert q.push(b'item%d' % i)
    assert q.qsize() == 4
    for i in range(4):
        assert q.pop() == b'item%d' % i
    q.close()
    assert q.pop() is None  # closed + drained
    assert not q.push(b'late')  # push after close fails


def test_queue_blocking_backpressure():
    q = NativeQueue(capacity=2)
    results = []

    def producer():
        for i in range(10):
            q.push(bytes([i]))
        q.close()

    t = threading.Thread(target=producer)
    t.start()
    while True:
        b = q.pop()
        if b is None:
            break
        results.append(b[0])
    t.join(5)
    assert results == list(range(10))  # bounded queue, order preserved


def test_queue_multi_producer_consumer_totals():
    q = NativeQueue(capacity=8)
    n_prod, per = 4, 50
    seen = []
    seen_lock = threading.Lock()
    done = threading.Barrier(n_prod + 1)

    def producer(k):
        for i in range(per):
            q.push(b'%d:%d' % (k, i))
        done.wait()

    def consumer():
        while True:
            b = q.pop()
            if b is None:
                return
            with seen_lock:
                seen.append(b)

    cons = [threading.Thread(target=consumer) for _ in range(3)]
    for c in cons:
        c.start()
    prods = [threading.Thread(target=producer, args=(k,))
             for k in range(n_prod)]
    for p in prods:
        p.start()
    done.wait()  # all producers finished
    q.close()
    for t in prods + cons:
        t.join(5)
    assert len(seen) == n_prod * per
    assert len(set(seen)) == n_prod * per  # no dupes, no losses


def test_recordio_native_roundtrip(tmp_path):
    path = str(tmp_path / 'native.rio')
    payloads = [b'alpha', b'', b'x' * 10000, np.arange(100).tobytes()]
    with NativeRecordWriter(path) as w:
        for p in payloads:
            w.write(p)
    got = list(NativeRecordReader(path))
    assert got == payloads


@pytest.mark.skipif(not available(), reason="needs the C++ runtime")
def test_recordio_cross_compat(tmp_path):
    """python writer <-> native reader and vice versa: the wire format is
    one format (io_recordio.py is the authority)."""
    payloads = [b'one', b'two' * 1000, b'']
    py_path = str(tmp_path / 'py.rio')
    io_recordio.write_records(py_path, payloads)
    assert list(NativeRecordReader(py_path)) == payloads

    nat_path = str(tmp_path / 'nat.rio')
    with NativeRecordWriter(nat_path) as w:
        for p in payloads:
            w.write(p)
    assert list(io_recordio.read_records(nat_path)) == payloads


@pytest.mark.skipif(not available(), reason="needs the C++ runtime")
def test_recordio_crc_detects_corruption(tmp_path):
    path = str(tmp_path / 'corrupt.rio')
    with NativeRecordWriter(path) as w:
        w.write(b'payload-payload')
    with open(path, 'r+b') as f:
        f.seek(-3, os.SEEK_END)
        f.write(b'XXX')
    with pytest.raises(IOError, match='crc'):
        list(NativeRecordReader(path))


def test_staging_arena_reuse():
    arena = StagingArena(block_size=1024, blocks=2)
    assert arena.free_blocks() == 2
    mv1, tok1 = arena.acquire()
    mv2, tok2 = arena.acquire()
    assert arena.free_blocks() == 0
    mv1[:5] = b'hello'
    arr = np.frombuffer(mv1, dtype=np.uint8, count=5)
    assert bytes(arr) == b'hello'
    del arr, mv1, mv2
    arena.release(tok1)
    arena.release(tok2)
    assert arena.free_blocks() == 2
    # reacquire reuses a released block (no new allocation)
    mv3, tok3 = arena.acquire()
    assert len(mv3) == 1024
    del mv3
    arena.release(tok3)


def test_prefetch_reader_equivalence():
    def source():
        for i in range(100):
            yield (np.full((4,), i, np.float32), i)

    got = list(prefetch_reader(source, buf_size=8)())
    assert len(got) == 100
    for i, (arr, lab) in enumerate(got):
        assert lab == i
        np.testing.assert_array_equal(arr, np.full((4,), i, np.float32))


def test_xmap_native_unordered_and_ordered():
    def source():
        for i in range(50):
            yield i

    mapped = list(xmap_native(lambda x: x * 2, source, process_num=4,
                              buffer_size=8)())
    assert sorted(mapped) == [2 * i for i in range(50)]

    ordered = list(xmap_native(lambda x: x * 3, source, process_num=4,
                               buffer_size=8, order=True)())
    assert ordered == [3 * i for i in range(50)]


def test_dataset_convert_recordio_roundtrip(tmp_path):
    """datasets.common.convert -> reader.creator.recordio round trip
    (V3 dataset cache over the N3 record format), multiple chunk files."""
    from paddle_tpu.datasets import common
    from paddle_tpu.reader import creator

    samples = [(np.arange(4, dtype='float32') + i, i) for i in range(10)]

    def source():
        return iter(samples)

    out = str(tmp_path)
    common.convert(out, source, line_count=3, name_prefix='unit')
    files = sorted(os.listdir(out))
    assert len(files) == 4  # 10 samples / 3 per chunk
    got = list(creator.recordio([os.path.join(out, f)
                                 for f in files])())
    assert len(got) == 10
    for (arr, lab), (w_arr, w_lab) in zip(got, samples):
        assert lab == w_lab
        np.testing.assert_array_equal(arr, w_arr)


def test_buffered_creator_surfaces_corruption(tmp_path):
    """A CRC error mid-stream re-raises through the buffered readahead
    instead of silently truncating the dataset."""
    import pickle
    path = str(tmp_path / 'corrupt.rio')
    with NativeRecordWriter(path) as w:
        for i in range(5):
            w.write(pickle.dumps(i))
    with open(path, 'r+b') as f:
        f.seek(-2, os.SEEK_END)
        f.write(b'XX')
    from paddle_tpu.reader import creator
    with pytest.raises((IOError, OSError)):
        list(creator.recordio(path)())  # default buffered path


def test_record_reader_close_then_next_stops(tmp_path):
    path = str(tmp_path / 'c.rio')
    with NativeRecordWriter(path) as w:
        w.write(b'one')
        w.write(b'two')
    r = NativeRecordReader(path)
    assert next(r) == b'one'
    r.close()
    with pytest.raises(StopIteration):
        next(r)


def test_creator_np_array_and_text_file(tmp_path):
    from paddle_tpu.reader import creator

    arr = np.arange(6).reshape(3, 2)
    rows = list(creator.np_array(arr)())
    assert len(rows) == 3
    np.testing.assert_array_equal(rows[1], [2, 3])

    p = tmp_path / 'lines.txt'
    p.write_text('alpha\nbeta\n')
    assert list(creator.text_file(str(p))()) == ['alpha', 'beta']


def test_feed_pipeline_streams_device_batches():
    from paddle_tpu.runtime import FeedPipeline

    n_steps = 12

    def fill(views, step):
        if step >= n_steps:
            return False
        views['x'][:] = step
        views['y'][:] = step * 2

    pipe = FeedPipeline(
        {'x': ((4, 8), np.float32), 'y': ((4, 1), np.int32)}, fill,
        depth=3)
    got = list(pipe)
    assert len(got) == n_steps
    for i, feed in enumerate(got):
        np.testing.assert_array_equal(np.asarray(feed['x']),
                                      np.full((4, 8), i, np.float32))
        np.testing.assert_array_equal(np.asarray(feed['y']),
                                      np.full((4, 1), 2 * i, np.int32))


def test_xmap_native_mapper_error_propagates_no_hang():
    def source():
        for i in range(20):
            yield i

    def bad_mapper(x):
        if x == 7:
            raise ValueError("corrupt sample")
        return x

    with pytest.raises(ValueError, match='corrupt sample'):
        list(xmap_native(bad_mapper, source, process_num=3,
                         buffer_size=4)())


def test_record_reader_exhaustion_keeps_raising(tmp_path):
    path = str(tmp_path / 'r.rio')
    with NativeRecordWriter(path) as w:
        w.write(b'one')
    r = NativeRecordReader(path)
    assert list(r) == [b'one']
    with pytest.raises(StopIteration):
        next(r)  # must raise again, not crash on the closed handle
    with pytest.raises(StopIteration):
        next(r)
    w2 = NativeRecordWriter(str(tmp_path / 'w.rio'))
    w2.close()
    if available():
        with pytest.raises(ValueError, match='closed'):
            w2.write(b'late')


def test_feed_pipeline_fill_error_raises():
    from paddle_tpu.runtime import FeedPipeline

    def fill(views, step):
        if step == 2:
            raise IOError("shard unreadable")
        views['x'][:] = step

    pipe = FeedPipeline({'x': ((2,), np.float32)}, fill, depth=2)
    with pytest.raises(RuntimeError, match='producer failed'):
        list(pipe)


def test_xmap_readers_uses_native_backend():
    from paddle_tpu.reader.decorator import xmap_readers

    def source():
        for i in range(20):
            yield i

    out = list(xmap_readers(lambda x: x + 1, source, 2, 4)())
    assert sorted(out) == list(range(1, 21))


def test_feed_pipeline_multiworker_preserves_order():
    """workers=3: fills run concurrently but batches arrive in step
    order (worker w owns steps w, w+N, ...; consumer round-robins)."""
    import numpy as np

    from paddle_tpu.runtime.feed import FeedPipeline

    n = 11

    def fill(views, step):
        if step >= n:
            return False
        views['x'][...] = step
        return True

    pipe = FeedPipeline({'x': ((4,), np.float32)}, fill, depth=6,
                        workers=3)
    got = [int(np.asarray(f['x'])[0]) for f in pipe]
    assert got == list(range(n)), got
    pipe.close()


def test_feed_pipeline_multiworker_propagates_error():
    import numpy as np
    import pytest

    from paddle_tpu.runtime.feed import FeedPipeline

    def fill(views, step):
        if step == 4:
            raise ValueError("boom")
        views['x'][...] = step
        return True

    pipe = FeedPipeline({'x': ((2,), np.float32)}, fill, depth=6,
                        workers=2)
    with pytest.raises(RuntimeError, match="producer failed"):
        for i, f in enumerate(pipe):
            if i > 16:  # the error step must surface promptly
                break
    pipe.close()
