"""Dynamic batching: the batch-1 queue contract on top of real device
batches (reference streams single frames, reference src/test.py:52-54;
the TPU wants batch 256)."""

import queue
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu.api import DEFER
from defer_tpu.config import DeferConfig
from defer_tpu.runtime.batching import BatchGatherer, split_output
from defer_tpu.runtime.host_io import STOP
from tests.test_partition import residual_chain


def test_gatherer_fills_a_batch():
    q: "queue.Queue" = queue.Queue()
    for i in range(4):
        q.put(jnp.full((1, 8), float(i)))
    g = BatchGatherer(batch_size=4, max_wait_s=5.0)
    batch, sizes, eos = g.gather(q)
    assert batch.shape == (4, 8) and sizes == [1, 1, 1, 1] and not eos
    assert [float(batch[i, 0]) for i in range(4)] == [0.0, 1.0, 2.0, 3.0]


def test_gatherer_slo_flushes_partial_batch():
    q: "queue.Queue" = queue.Queue()
    q.put(jnp.ones((2, 8)))
    g = BatchGatherer(batch_size=64, max_wait_s=0.05)
    batch, sizes, eos = g.gather(q)
    assert batch.shape == (2, 8) and sizes == [2] and not eos


def test_gatherer_idle_and_sentinel():
    q: "queue.Queue" = queue.Queue()
    g = BatchGatherer(batch_size=4, max_wait_s=0.01)
    assert g.gather(q, poll_s=0.01) == (None, None, False)
    q.put(STOP)
    assert g.gather(q) == (None, None, True)
    q.put(None)
    assert g.gather(q) == (None, None, True)


def test_gatherer_sentinel_mid_batch_flushes():
    q: "queue.Queue" = queue.Queue()
    q.put(jnp.ones((1, 8)))
    q.put(jnp.ones((1, 8)) * 2)
    q.put(None)
    g = BatchGatherer(batch_size=8, max_wait_s=5.0)
    batch, sizes, eos = g.gather(q)
    assert batch.shape == (2, 8) and sizes == [1, 1] and eos


def test_gatherer_mismatch_carries():
    q: "queue.Queue" = queue.Queue()
    q.put(jnp.ones((1, 8)))
    q.put(jnp.ones((1, 16)))  # different trailing shape
    g = BatchGatherer(batch_size=4, max_wait_s=0.2)
    b1, s1, _ = g.gather(q)
    assert b1.shape == (1, 8) and s1 == [1]
    assert g.pending()
    b2, s2, _ = g.gather(q)
    assert b2.shape == (1, 16) and s2 == [1]
    assert not g.pending()


def test_gatherer_varying_item_batch_dims():
    q: "queue.Queue" = queue.Queue()
    q.put(jnp.ones((2, 8)))
    q.put(jnp.full((3, 8), 2.0))
    g = BatchGatherer(batch_size=8, max_wait_s=0.2)
    batch, sizes, _ = g.gather(q)
    # total 5 pads up to the 8 bucket; sizes still sum to the real 5.
    assert batch.shape == (8, 8) and sizes == [2, 3]
    parts = split_output(batch, sizes)
    assert parts[0].shape == (2, 8) and parts[1].shape == (3, 8)
    assert float(parts[1][0, 0]) == 2.0


def test_gatherer_rejects_degenerate_size():
    with pytest.raises(ValueError, match="batch_size >= 2"):
        BatchGatherer(batch_size=1, max_wait_s=0.1)


def test_run_defer_dynamic_batching_end_to_end(devices, monkeypatch):
    """20 batch-1 items through run_defer with dynamic_batch_size=4:
    per-item outputs in order with correct values, and the device saw
    FEWER dispatches than items (batching actually happened)."""
    from defer_tpu.parallel.pipeline import Pipeline

    dispatch_batches = []
    orig_submit = Pipeline.submit

    def counting_submit(self, x):
        dispatch_batches.append(int(x.shape[0]))
        return orig_submit(self, x)

    monkeypatch.setattr(Pipeline, "submit", counting_submit)

    g = residual_chain()
    params = g.init(jax.random.key(0), (1, 8))
    cfg = DeferConfig(
        compute_dtype=jnp.float32, dynamic_batch_size=4, batch_wait_s=0.2
    )
    defer = DEFER(config=cfg)
    inq: "queue.Queue" = queue.Queue()
    outq: "queue.Queue" = queue.Queue()
    xs = [jnp.full((1, 8), float(i)) for i in range(20)]
    # Pre-fill before starting so the gatherer sees full batches.
    for x in xs:
        inq.put(x)
    inq.put(None)
    t = threading.Thread(
        target=defer.run_defer,
        args=(g, ["add_1"], inq, outq),
        kwargs={"params": params},
        daemon=True,
    )
    t.start()
    outs = [outq.get(timeout=120) for _ in range(20)]
    t.join(timeout=120)
    assert not t.is_alive()
    for x, out in zip(xs, outs):
        assert out.shape == (1, g.apply(params, x).shape[-1])
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(g.apply(params, x)), rtol=1e-5
        )
    assert len(dispatch_batches) < 20, dispatch_batches
    assert max(dispatch_batches) == 4, dispatch_batches


def test_gatherer_pads_partial_batches_to_buckets():
    """Bursty partial flushes must land on power-of-two buckets so the
    jitted stages see a bounded set of leading dims (each distinct size
    is a full recompile)."""
    q: "queue.Queue" = queue.Queue()
    for _ in range(3):
        q.put(jnp.ones((1, 8)))
    g = BatchGatherer(batch_size=64, max_wait_s=0.05)
    batch, sizes, _ = g.gather(q)
    assert sizes == [1, 1, 1]
    assert batch.shape == (4, 8)  # padded 3 -> 4
    parts = split_output(batch, sizes)
    assert len(parts) == 3 and all(p.shape == (1, 8) for p in parts)
    # A full batch is not padded.
    for _ in range(4):
        q.put(jnp.ones((16, 8)))
    g2 = BatchGatherer(batch_size=64, max_wait_s=1.0)
    b2, s2, _ = g2.gather(q)
    assert b2.shape == (64, 8) and s2 == [16, 16, 16, 16]


def test_gatherer_rejects_scalar_items():
    q: "queue.Queue" = queue.Queue()
    q.put(jnp.float32(3.0))
    g = BatchGatherer(batch_size=4, max_wait_s=0.01)
    with pytest.raises(ValueError, match="leading"):
        g.gather(q)


def test_transport_quantize_non_finite_falls_back_lossless():
    import numpy as onp

    from defer_tpu.runtime.transport import ArrayReceiver, ArraySender

    recv = ArrayReceiver(port=0)
    got = []
    t = threading.Thread(target=lambda: got.extend(recv), daemon=True)
    t.start()
    snd = ArraySender("127.0.0.1", recv.port, quantize="int8")
    bad = onp.array([1.0, onp.inf, onp.nan], onp.float32)
    snd.send(bad)
    snd.close()
    t.join(timeout=30)
    assert not t.is_alive() and len(got) == 1
    onp.testing.assert_array_equal(got[0], bad)  # lossless, NaN/Inf kept


def test_single_padded_item_does_not_leak_pad_rows():
    """A lone (3, C) item padded to the 4-bucket must come back as
    (3, C) — pad rows are garbage, not results."""
    q: "queue.Queue" = queue.Queue()
    q.put(jnp.ones((3, 8)))
    g = BatchGatherer(batch_size=64, max_wait_s=0.05)
    batch, sizes, _ = g.gather(q)
    assert batch.shape == (4, 8) and sizes == [3]
    parts = split_output(batch, sizes)
    assert len(parts) == 1 and parts[0].shape == (3, 8)


def test_gather_bounds_rows_not_item_count():
    """batch_size caps device ROWS: (3, C) items with batch_size=8 stop
    at 2 items (6 rows; a third would overflow) and the overflow item
    carries to the next batch."""
    q: "queue.Queue" = queue.Queue()
    for _ in range(3):
        q.put(jnp.ones((3, 8)))
    g = BatchGatherer(batch_size=8, max_wait_s=1.0)
    b1, s1, _ = g.gather(q)
    assert s1 == [3, 3]
    assert b1.shape == (8, 8)  # 6 rows padded to the 8 bucket
    assert g.pending()
    b2, s2, _ = g.gather(q)
    assert s2 == [3] and b2.shape == (4, 8)


def test_deadline_budget_machinery():
    """Deadline is the shared monotonic budget both the gatherer's
    flush SLO and fleet admission's enqueue wait run on: remaining
    shrinks, elapsed grows, expiry is a one-way door."""
    import time

    from defer_tpu.runtime.batching import Deadline

    dl = Deadline(0.05)
    assert not dl.expired()
    r0 = dl.remaining()
    assert 0 < r0 <= 0.05
    time.sleep(0.06)
    assert dl.expired()
    assert dl.remaining() <= 0
    assert dl.elapsed() >= 0.06
