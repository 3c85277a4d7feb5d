"""Streaming generators (`num_returns="streaming"`) — reference parity:
_raylet.pyx:280 ObjectRefGenerator. Incremental refs from task and actor
generators, error-as-final-ref semantics, backpressure, async actors."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu import ObjectRefGenerator
from ray_tpu.exceptions import TaskError


def test_task_generator_streams(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * 10

    g = gen.remote(5)
    assert isinstance(g, ObjectRefGenerator)
    vals = [ray_tpu.get(ref) for ref in g]
    assert vals == [0, 10, 20, 30, 40]


def test_incremental_delivery(ray_start_regular):
    """The first value is in hand before the generator has produced the
    second: held to the generator's own stamp of its second yield (one
    host, one clock), not to a wall budget that a worker's spawn on a
    loaded box would eat."""
    import time

    @ray_tpu.remote(num_returns="streaming")
    def slow_gen():
        yield "first"
        time.sleep(2.0)
        yield time.time()            # when the second value was made

    g = slow_gen.remote()
    assert ray_tpu.get(next(g)) == "first"
    in_hand = time.time()
    second_made = ray_tpu.get(next(g))
    assert in_hand < second_made     # did not wait for the full generator
    with pytest.raises(StopIteration):
        next(g)


def test_generator_error_surfaces_as_final_ref(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def bad():
        yield 1
        raise ValueError("boom")

    g = bad.remote()
    assert ray_tpu.get(next(g)) == 1
    with pytest.raises(TaskError):
        ray_tpu.get(next(g))
    with pytest.raises(StopIteration):
        next(g)


def test_large_values_stream_through_shm(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def chunks():
        for i in range(3):
            yield np.full((300_000,), float(i))

    for i, ref in enumerate(chunks.remote()):
        arr = ray_tpu.get(ref)
        assert arr[0] == float(i) and arr.shape == (300_000,)


def test_backpressure_bounds_producer(ray_start_regular):
    @ray_tpu.remote(
        num_returns="streaming", _generator_backpressure_num_objects=2
    )
    def gen():
        import os, time

        for i in range(6):
            yield i

    g = gen.remote()
    # consume slowly; producer must not run unboundedly ahead (it blocks
    # on credit after 2 unconsumed). Just verify full delivery/order.
    out = [ray_tpu.get(r) for r in g]
    assert out == list(range(6))


def test_actor_sync_generator(ray_start_regular):
    @ray_tpu.remote
    class Gen:
        def stream(self, n):
            for i in range(n):
                yield f"item{i}"

    a = Gen.remote()
    vals = [
        ray_tpu.get(r)
        for r in a.stream.options(num_returns="streaming").remote(3)
    ]
    assert vals == ["item0", "item1", "item2"]


def test_actor_async_generator(ray_start_regular):
    @ray_tpu.remote
    class AGen:
        async def stream(self, n):
            import asyncio

            for i in range(n):
                await asyncio.sleep(0.01)
                yield i * i

    a = AGen.remote()
    vals = [
        ray_tpu.get(r)
        for r in a.stream.options(num_returns="streaming").remote(4)
    ]
    assert vals == [0, 1, 4, 9]


def test_failure_before_first_yield_ends_stream(ray_start_regular):
    """Arg-binding/decode errors happen before the generator exists; the
    stream must still end with the error (review finding: consumer hung
    forever otherwise)."""

    @ray_tpu.remote(num_returns="streaming")
    def gen(a, b):
        yield a + b

    g = gen.remote(1)  # TypeError: missing positional arg
    with pytest.raises(TaskError):
        ray_tpu.get(next(g), timeout=15)
    with pytest.raises(StopIteration):
        next(g)


def test_worker_death_ends_stream_with_error(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def dies():
        import os

        yield 1
        os._exit(1)

    g = dies.remote()
    assert ray_tpu.get(next(g)) == 1
    with pytest.raises(Exception):
        ray_tpu.get(next(g), timeout=10)


def _count_requests(monkeypatch):
    """{message type: requests this process's core client sent}."""
    from ray_tpu._private import worker

    client, sent = worker.get_client(), {}
    inner = client.request

    def counted(msg_type, payload, **kw):
        sent[msg_type] = sent.get(msg_type, 0) + 1
        return inner(msg_type, payload, **kw)

    monkeypatch.setattr(client, "request", counted)
    return sent


def _wait_until_all_yielded(g, timeout_s=60.0):
    """Until the generator's task is done: every item is at the hub."""
    import time

    from ray_tpu._private import worker

    client, want = worker.get_client(), g._task_id.hex()
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if any(t["task_id"] == want and t.get("state") == "FINISHED"
               for t in client.list_state("tasks")):
            return
        time.sleep(0.05)
    raise AssertionError("the generator's task never finished")


@pytest.mark.parametrize("items", [1, 7, 150])
def test_a_consumer_behind_is_handed_what_queued_up(
        ray_start_regular, monkeypatch, items):
    """Items that are there when the consumer asks come in one reply (64
    at the most), their inline values with them: iterating and getting
    them all costs a STREAM_NEXT a batch and the one that finds the end,
    and no GET."""
    from ray_tpu._private import protocol as P

    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i

    g = gen.remote(items)
    _wait_until_all_yielded(g)
    sent = _count_requests(monkeypatch)
    assert [ray_tpu.get(ref) for ref in g] == list(range(items))
    assert sent.get(P.GET, 0) == 0
    assert sent[P.STREAM_NEXT] == -(-items // ObjectRefGenerator._BATCH) + 1


def test_a_bounded_producers_consumer_is_handed_one_item_a_reply(
        ray_start_regular, monkeypatch):
    """A producer that asks for credit counts what the consumer has
    read: its items are not handed over ahead of the reading."""
    import time

    from ray_tpu._private import protocol as P

    @ray_tpu.remote(
        num_returns="streaming", _generator_backpressure_num_objects=2
    )
    def gen():
        for i in range(6):
            yield i

    g = gen.remote()
    time.sleep(1.0)
    sent = _count_requests(monkeypatch)
    assert [ray_tpu.get(r) for r in g] == list(range(6))
    assert sent[P.STREAM_NEXT] == 7 and sent.get(P.GET, 0) == 0


def test_a_large_streamed_value_is_still_fetched_by_its_get(
        ray_start_regular, monkeypatch):
    from ray_tpu._private import protocol as P

    @ray_tpu.remote(num_returns="streaming")
    def chunks():
        yield np.ones((300_000,))
        yield 5

    g = chunks.remote()
    sent = _count_requests(monkeypatch)
    big, small = [ray_tpu.get(ref) for ref in g]
    assert big.shape == (300_000,) and small == 5
    assert sent.get(P.GET, 0) == 1


@pytest.mark.parametrize("first_ask_after_s", [0.0, 0.02, 0.2])
def test_a_bounded_producer_stays_within_its_bound_of_what_was_read(
        ray_start_regular, tmp_path, monkeypatch, first_ask_after_s):
    """The bound holds from the first item, whenever the consumer first
    asks (at once, between the first yields and the producer's first
    wait for credit, or behind it): the producer is never more than its
    bound ahead of the items the consumer has taken, and every reply
    holds one item."""
    import time

    from ray_tpu._private import protocol as P

    bound, items, log = 2, 9, tmp_path / "yielded"

    @ray_tpu.remote(
        num_returns="streaming", _generator_backpressure_num_objects=bound
    )
    def gen(path):
        for i in range(items):
            with open(path, "a") as f:
                f.write("x")
            yield i

    g = gen.remote(str(log))
    time.sleep(first_ask_after_s)
    sent = _count_requests(monkeypatch)
    for taken in range(1, items + 1):
        assert ray_tpu.get(next(g)) == taken - 1
        if taken in (1, 4):
            time.sleep(0.5)              # the producer runs as far as it may
            # it writes the log before the yield that may have to wait
            assert len(log.read_text()) <= taken + bound + 1
    with pytest.raises(StopIteration):
        next(g)
    assert sent[P.STREAM_NEXT] == items + 1 and sent.get(P.GET, 0) == 0


@pytest.mark.parametrize("bound", [True, False])
def test_the_bound_holds_before_the_producers_first_wait_for_credit(
        ray_start_regular, bound):
    """A consumer's STREAM_NEXT that lands between a bounded producer's
    first yields and its first STREAM_CREDIT is handed one item, not
    all that are there: the test is the producer, so no credit was ever
    asked for."""
    import os

    from ray_tpu._private import protocol as P
    from ray_tpu._private import worker
    from ray_tpu._private.ids import ObjectID

    client, task_id = worker.get_client(), os.urandom(16)
    for value in (10, 11, 12):
        oid = ObjectID.generate()
        kind, payload, size = client.encode_value(oid, value)
        item = {"task_id": task_id, "object_id": oid.binary(), "kind": kind,
                "payload": payload, "size": size, "t_wall": None}
        if bound:
            item["bound"] = True
        client.send(P.STREAM_YIELD, item)
    reply = client.request(
        P.STREAM_NEXT, {"task_id": task_id, "index": 0, "batch": 64})
    assert len(reply["items"]) == (1 if bound else 3)
    client.send(P.STREAM_END, {"task_id": task_id, "error": None})


def _stream_counters():
    """The hub's ``ray_tpu_stream_*_total`` counters, by their middles."""
    from ray_tpu.util import metrics

    return {m["name"][len("ray_tpu_stream_"):-len("_total")]: m["value"]
            for m in metrics.snapshot()
            if m["name"].startswith("ray_tpu_stream_")}


def test_a_streamed_item_carries_the_hubs_two_stamps(ray_start_regular):
    """Beside the producer's yield stamp an item comes with the hub's
    stamp of its STREAM_YIELD, and its reply with the hub's stamp of the
    send: in that order on one host's clock, whether the consumer waited
    for the item (parked) or the item for the consumer (found, several
    in one reply). The hub counts items, replies and who waited."""
    import time

    from ray_tpu.util import tracing

    @ray_tpu.remote(num_returns="streaming")
    def slow_then_fast():
        time.sleep(0.3)              # the consumer is waiting by then
        for i in range(4):
            yield i

    before = _stream_counters()
    g = slow_then_fast.remote()
    assert (g.last_hub_wall, g.last_reply_wall, g.last_next_wait_s) == (
        None, None, None)
    rows = []

    def take(i):
        ref = next(g)
        rows.append((g.last_yield_wall, g.last_hub_wall, g.last_reply_wall,
                     g.last_next_wait_s))
        assert ray_tpu.get(ref) == i

    take(0)
    time.sleep(0.3)                  # items 1 to 3 are there before the ask
    for i in (1, 2, 3):
        take(i)
    with pytest.raises(StopIteration):
        next(g)
    now = tracing.wall_at(time.monotonic())
    jitter = 1e-3                    # two processes' anchors
    for t_wall, t_hub, t_reply, _ in rows:
        assert t_wall - jitter <= t_hub <= t_reply <= now + jitter
    # item 0 was waited for: handled and replied in one pass of the hub
    assert rows[0][2] - rows[0][1] < 0.1 and rows[0][3] >= 0.25
    # items 1 to 3 waited in the hub for the consumer's sleep, and came
    # in one reply: one stamp of the send, one round trip
    assert all(r[2] - r[1] >= 0.25 for r in rows[1:])
    assert len({r[2] for r in rows[1:]}) == 1
    assert rows[1][3] is not None and (rows[2][3], rows[3][3]) == (None, None)
    grew = {k: v - before.get(k, 0) for k, v in _stream_counters().items()}
    assert grew["items"] == 4 and grew["next_replies"] == 2
    # the first ask waited for its item, the second found three; the
    # third found the end, or waited for it
    assert grew["next_found"] == 1 and grew["next_parked"] in (1, 2)
    assert "credit_stalls" in grew


def test_a_reply_without_the_hubs_stamps_reads_as_before(monkeypatch):
    """A hub from before the stamps sends three fields an item and no
    ``t_reply``: the generator hands the refs on with the yield stamp
    alone."""
    from ray_tpu._private import worker
    from ray_tpu._private.ids import ObjectID

    oids = [ObjectID.generate().binary() for _ in range(2)]

    class OldHubsClient:
        held = []

        def request(self, msg_type, payload):
            if payload["index"] >= len(oids):
                return {"end": True}
            return {"items": [(oid, 100.0 + i, b"v")
                              for i, oid in enumerate(oids)]}

        def hold_inline(self, oid, payload):
            self.held.append(oid)

    client = OldHubsClient()
    monkeypatch.setattr(worker, "get_client", lambda: client)
    g = ObjectRefGenerator(b"t" * 16)
    seen = []
    for ref in g:
        seen.append((ref.binary(), g.last_yield_wall, g.last_hub_wall,
                     g.last_reply_wall))
    assert seen == [(oids[0], 100.0, None, None), (oids[1], 101.0, None, None)]
    assert client.held == oids
