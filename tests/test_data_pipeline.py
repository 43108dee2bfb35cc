"""Data pipeline: reader decorators (reference reader/decorator.py +
tests/decorator_test.py), recordio writer/scanner (reference
paddle/fluid/recordio/*_test.cc), dataset adapters, and the
double-buffered DeviceLoader (reference operators/reader/)."""
import os
import struct
import zlib

import numpy as np
import pytest

import paddle_tpu.reader as reader
from paddle_tpu import dataset, recordio


# --------------------------- decorators ---------------------------------

def _counter(n):
    def r():
        for i in range(n):
            yield i

    return r


def test_map_readers():
    got = list(reader.map_readers(lambda a, b: a + b,
                                  _counter(4), _counter(4))())
    assert got == [0, 2, 4, 6]


def test_shuffle_is_permutation():
    got = list(reader.shuffle(_counter(20), 7)())
    assert sorted(got) == list(range(20))


def test_chain_and_firstn():
    got = list(reader.firstn(reader.chain(_counter(3), _counter(3)), 5)())
    assert got == [0, 1, 2, 0, 1]


def test_compose_flattens_and_checks_alignment():
    def pairs():
        for i in range(3):
            yield (i, i * 10)

    got = list(reader.compose(_counter(3), lambda: pairs())())
    assert got == [(0, 0, 0), (1, 1, 10), (2, 2, 20)]
    with pytest.raises(reader.ComposeNotAligned):
        list(reader.compose(_counter(3), _counter(5))())
    # alignment off: stops at the shortest
    got = list(reader.compose(_counter(3), _counter(5),
                              check_alignment=False)())
    assert len(got) == 3


def test_buffered_and_cache():
    assert list(reader.buffered(_counter(10), 3)()) == list(range(10))
    calls = []

    def tracked():
        calls.append(1)
        for i in range(4):
            yield i

    c = reader.cache(tracked)
    assert list(c()) == list(range(4))
    assert list(c()) == list(range(4))
    assert len(calls) == 1  # second epoch replayed from memory


@pytest.mark.parametrize("order", [False, True])
def test_xmap_readers(order):
    got = list(reader.xmap_readers(lambda x: x * x, _counter(20), 4, 8,
                                   order=order)())
    if order:
        assert got == [i * i for i in range(20)]
    else:
        assert sorted(got) == sorted(i * i for i in range(20))


def test_batch():
    got = list(reader.batch(_counter(7), 3)())
    assert got == [[0, 1, 2], [3, 4, 5]]
    got = list(reader.batch(_counter(7), 3, drop_last=False)())
    assert got[-1] == [6]


# ---------------------------- recordio ----------------------------------

RECS = [b"a", b"", b"z" * 4096, bytes(range(256))]


@pytest.mark.parametrize("wn,rn", [(True, True), (True, False),
                                   (False, True), (False, False)])
def test_recordio_roundtrip_cross_impl(tmp_path, wn, rn):
    """C++ and Python codecs produce/consume the same on-disk format."""
    if (wn or rn) and not recordio.native_available():
        pytest.skip("no native toolchain")
    p = str(tmp_path / "r.rio")
    recordio.write_records(p, RECS, use_native=wn)
    assert list(recordio.read_records(p, use_native=rn)) == RECS


def test_recordio_skips_corrupt_chunk(tmp_path):
    p = str(tmp_path / "c.rio")
    recordio.write_records(p, RECS, use_native=False)
    raw = struct.pack("<I", 2) + b"ok"
    stored = zlib.compress(raw)
    hdr = struct.Struct("<6I")
    with open(p, "ab") as f:
        f.write(hdr.pack(recordio.MAGIC, recordio.ZLIB, 1, len(raw),
                         len(stored), 0xBAD))   # wrong crc -> skipped
        f.write(stored)
        f.write(hdr.pack(recordio.MAGIC, recordio.ZLIB, 1, len(raw),
                         len(stored), zlib.crc32(stored)))
        f.write(stored)
    for native in ([True, False] if recordio.native_available()
                   else [False]):
        assert list(recordio.read_records(p, use_native=native)) == \
            RECS + [b"ok"]


def test_recordio_reader_creator(tmp_path):
    p = str(tmp_path / "n.rio")
    arrs = [np.arange(4, dtype=np.float32) * i for i in range(5)]
    recordio.write_records(p, [a.tobytes() for a in arrs])
    got = list(reader.creator.recordio(
        p, deserializer=lambda b: np.frombuffer(b, np.float32))())
    for g, a in zip(got, arrs):
        np.testing.assert_array_equal(g, a)


# ---------------------------- datasets ----------------------------------

def test_mnist_shapes():
    it = dataset.mnist.train()()
    img, lab = next(it)
    assert img.shape == (784,) and img.dtype == np.float32
    assert -1.0 <= img.min() and img.max() <= 1.0
    assert isinstance(lab, int) and 0 <= lab < 10


def test_cifar_shapes():
    img, lab = next(dataset.cifar.train10()())
    assert img.shape == (3072,) and img.dtype == np.float32
    assert 0 <= lab < 10
    img, lab = next(dataset.cifar.train100()())
    assert 0 <= lab < 100


def test_uci_housing_learnable():
    xs, ys = zip(*list(dataset.uci_housing.train()()))
    x, y = np.stack(xs), np.stack(ys)
    assert x.shape[1] == 13
    # linear regression closed form fits it well (synthetic is linear;
    # the real dataset also has strong linear signal)
    w, *_ = np.linalg.lstsq(
        np.concatenate([x, np.ones((len(x), 1), np.float32)], 1), y,
        rcond=None)
    pred = np.concatenate([x, np.ones((len(x), 1), np.float32)], 1) @ w
    rel = np.mean((pred - y) ** 2) / max(np.var(y), 1e-6)
    assert rel < 0.5


def test_dataset_split_and_cluster_reader(tmp_path):
    pat = str(tmp_path / "part-%05d.pickle")
    n = dataset.common.split(_counter(10), 3, suffix=pat)
    assert n == 4
    shard0 = list(dataset.common.cluster_files_reader(
        str(tmp_path / "part-*.pickle"), 2, 0)())
    shard1 = list(dataset.common.cluster_files_reader(
        str(tmp_path / "part-*.pickle"), 2, 1)())
    assert sorted(shard0 + shard1) == list(range(10))
    assert shard0 and shard1


def test_device_loader_early_break_stops_producer():
    """Abandoning the iterator mid-epoch must release the producer
    thread (no leaked thread pinning device-staged batches)."""
    import threading
    import time

    import paddle_tpu.fluid as fluid

    def slow_reader():
        for i in range(100):
            yield [(np.zeros(4, np.float32),) for _ in range(2)]

    before = threading.active_count()
    loader = reader.DeviceLoader(slow_reader, ["x"], fluid.CPUPlace(),
                                 capacity=2)
    it = iter(loader)
    next(it)
    it.close()  # generator finally -> stop event
    deadline = time.time() + 5
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before


# -------------------------- device loader -------------------------------

def test_device_loader_feeds_training():
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                img = fluid.layers.data(name="img", shape=[784],
                                        dtype="float32")
                lab = fluid.layers.data(name="label", shape=[1],
                                        dtype="int64")
                pred = fluid.layers.fc(img, size=10, act="softmax")
                loss = fluid.layers.mean(
                    fluid.layers.cross_entropy(pred, lab))
                fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)

        r = reader.batch(
            reader.shuffle(
                reader.map_readers(
                    lambda s: (s[0], np.asarray([s[1]], np.int64)),
                    dataset.mnist.train()),
                buf_size=256),
            batch_size=64)
        loader = reader.DeviceLoader(r, ["img", "label"],
                                     fluid.CPUPlace(), capacity=2)
        losses = []
        for feed in loader:
            l, = exe.run(main, feed=feed, fetch_list=[loss])
            losses.append(float(np.ravel(l)[0]))
        assert len(losses) == 2048 // 64
        # learnable synthetic blobs: one epoch must cut loss in half
        assert np.mean(losses[-4:]) < losses[0] * 0.5


def test_pipe_reader_plain_and_gzip(tmp_path):
    import gzip
    import os

    from paddle_tpu.reader import PipeReader

    pr = PipeReader("echo alpha beta")
    assert list(pr.get_line()) == ["alpha beta"]

    path = os.path.join(str(tmp_path), "x.gz")
    with gzip.open(path, "wb") as f:
        f.write(b"l1\nl2\nl3\n")
    pr = PipeReader("cat %s" % path, file_type="gzip")
    assert list(pr.get_line()) == ["l1", "l2", "l3"]


# ----------------------- DeviceDatasetCache -----------------------------

def _labeled_reader(n, dim=4):
    def r():
        for i in range(n):
            yield (np.full((dim,), i, np.float32),
                   np.asarray([i], np.int64))

    return r


def test_device_dataset_cache_epoch_coverage_and_reshuffle():
    import paddle_tpu.fluid as fluid

    n, bs = 20, 5
    cache = reader.DeviceDatasetCache(
        _labeled_reader(n), ["x", "y"], fluid.CPUPlace(), bs, seed=7)

    def epoch_ids():
        ids = []
        batches = 0
        for d in cache:
            assert d["x"].shape == (bs, 4)
            assert d["y"].shape == (bs, 1)
            # field alignment: the label matches the image fill value
            assert np.array_equal(np.asarray(d["x"])[:, 0],
                                  np.asarray(d["y"])[:, 0])
            ids.extend(np.asarray(d["y"])[:, 0].tolist())
            batches += 1
        assert batches == n // bs
        return ids

    e0, e1 = epoch_ids(), epoch_ids()
    # every sample exactly once per epoch, different order across epochs
    assert sorted(e0) == list(range(n))
    assert sorted(e1) == list(range(n))
    assert e0 != e1


def test_device_dataset_cache_budget_and_small_dataset():
    import paddle_tpu.fluid as fluid

    with pytest.raises(ValueError, match="max_bytes"):
        reader.DeviceDatasetCache(_labeled_reader(8), ["x", "y"],
                                  fluid.CPUPlace(), 2, max_bytes=16)
    with pytest.raises(ValueError, match="smaller than one batch"):
        reader.DeviceDatasetCache(_labeled_reader(3), ["x", "y"],
                                  fluid.CPUPlace(), 4)


def test_resnet_uint8_input_matches_float(tmp_path):
    """get_model(input_dtype='uint8') — device-side cast+scale gives the
    same forward loss as feeding img/255 as float32."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.models import resnet

    rng = np.random.RandomState(0)
    u8 = rng.randint(0, 256, (2, 3, 32, 32)).astype(np.uint8)
    lab = rng.randint(0, 10, (2, 1)).astype(np.int64)
    losses = {}
    for dt in ("uint8", "float32"):
        main, startup = fluid.Program(), fluid.Program()
        scope = Scope()
        with fluid.scope_guard(scope):
            with fluid.program_guard(main, startup):
                with fluid.unique_name.guard():
                    avg_cost, (data, label), _ = resnet.get_model(
                        data_set="cifar10", input_dtype=dt, is_test=True)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            feed = {data.name: u8 if dt == "uint8"
                    else (u8.astype(np.float32) / 255.0),
                    label.name: lab}
            loss, = exe.run(main, feed=feed, fetch_list=[avg_cost])
        losses[dt] = float(np.asarray(loss).ravel()[0])
    assert np.isfinite(losses["uint8"])
    assert abs(losses["uint8"] - losses["float32"]) < 1e-4


def test_device_loader_hides_producer_latency():
    """The double-buffer contract (reference
    create_double_buffer_reader_op.cc): reader latency (disk/network
    waits) hides behind compute — the streamed loop costs
    ~max(compute, produce), not the sum.  Pure H2D overlap is a
    hardware property the CPU backend cannot exhibit (its "transfer"
    is a memcpy on the same cores as compute; work is conserved) —
    bench.py's stream_overlap_ratio field reports that number on the
    real chip.  Reader latency here is a wall-clock sleep, so the
    assertion is load-independent."""
    import time

    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid

    place = fluid.CPUPlace()
    dev = place.jax_device()
    n_batches = 6
    field = np.random.RandomState(0).rand(1 << 20).astype(np.float32)
    prebuilt = [field + np.float32(i) for i in range(n_batches)]

    w = jax.device_put(np.random.RandomState(1).rand(1024, 1024)
                       .astype(np.float32), dev)

    @jax.jit
    def compute(x, w):
        acc = w
        for _ in range(8):
            acc = jnp.tanh(acc @ w)
        return acc.sum() + x.reshape(-1)[0]

    compute(jax.device_put(field[None], dev), w).block_until_ready()

    # per-batch compute time on THIS host: the reader delay is sized to
    # match it, so the overlappable quantity (min(compute, delay) per
    # steady-state batch) is a fixed fraction of the loop whatever the
    # machine's speed — a hard-coded delay made the bound unsatisfiable
    # on rigs whose compute runs faster than the delay (the streamed
    # loop is then reader-bound at ~n*delay, which can exceed
    # t_naive - hidden for ANY overlap quality)
    t0 = time.time()
    for i in range(n_batches):
        compute(jax.device_put(prebuilt[i][None], dev),
                w).block_until_ready()
    t_comp = (time.time() - t0) / n_batches
    delay = max(0.03, t_comp)

    def reader():
        for b in prebuilt:
            time.sleep(delay)
            yield [(b,)]

    # naive serial loop: read -> stage -> compute, one at a time
    t0 = time.time()
    for samples in reader():
        x = jax.device_put(np.stack([samples[0][0]])[None], dev)
        r = compute(x, w)
        r.block_until_ready()
    t_naive = time.time() - t0

    # double-buffered: reader sleeps overlap the running compute
    loader = pt.reader.DeviceLoader(reader, ["x"], place, capacity=3)
    t0 = time.time()
    for feed in loader:
        r = compute(feed["x"], w)
        r.block_until_ready()
    t_stream = time.time() - t0

    # the loader must hide most of the hideable time.  Hideable =
    # min(compute, delay) per steady-state batch; allow keeping one
    # pipeline-fill delay plus 1.5 more for scheduler noise.
    hideable = min(t_comp, delay)
    budget = t_naive - (n_batches - 2.5) * hideable
    assert t_stream < budget, (
        "reader latency not hidden: naive %.3fs, streamed %.3fs, "
        "budget %.3fs (compute %.3fs, delay %.3fs x %d batches)"
        % (t_naive, t_stream, budget, t_comp, delay, n_batches))
