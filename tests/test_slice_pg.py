"""SLICE placement-group tests: ICI-topology-aware chip reservation.

The TPU-native strategy the reference approximates with pod-name gang
resources (reference python/ray/_private/accelerators/tpu.py:352-375).
Covers: contiguous reservation on a line and a 2D mesh, fragmentation
correctly failing, unknown topology rejected at creation, tasks pinned
to their bundle's reserved chips, and get_current_placement_group.
"""

import os

import pytest

import ray_tpu
from ray_tpu.util import (
    PlacementGroupSchedulingStrategy,
    placement_group,
    placement_group_table,
    remove_placement_group,
)
from ray_tpu.util.placement_group import get_current_placement_group


@pytest.fixture
def slice_cluster(monkeypatch):
    monkeypatch.setenv("TPU_TOPOLOGY", "1x8")
    ctx = ray_tpu.init(
        num_cpus=4, num_tpus=8, max_workers=4, ignore_reinit_error=True
    )
    yield ctx
    ray_tpu.shutdown()


def _pg_entry(pg):
    return placement_group_table()[pg.id.hex()]


def _coords_1x8(chip):
    return (0, chip)  # x has one value, so y counts the chips


def _is_connected(chips, coords):
    """BFS connectivity over unit-step mesh adjacency."""
    chips = set(chips)
    if not chips:
        return False
    seen = {next(iter(chips))}
    frontier = list(seen)
    pos = {coords(c): c for c in chips}
    while frontier:
        c = frontier.pop()
        base = coords(c)
        for dim in range(len(base)):
            for d in (-1, 1):
                nb = list(base)
                nb[dim] += d
                n = pos.get(tuple(nb))
                if n is not None and n not in seen:
                    seen.add(n)
                    frontier.append(n)
    return seen == chips


def test_slice_reserves_contiguous_chips(slice_cluster):
    pg = placement_group([{"TPU": 2}, {"TPU": 2}], strategy="SLICE")
    assert pg.wait(10)
    entry = _pg_entry(pg)
    chips0, chips1 = entry["bundle_chips"]
    assert len(chips0) == 2 and len(chips1) == 2
    # each bundle's chips are ICI-connected, and the whole reservation
    # is one contiguous run on the 1x8 line
    assert _is_connected(chips0, _coords_1x8)
    assert _is_connected(chips1, _coords_1x8)
    assert _is_connected(chips0 + chips1, _coords_1x8)
    remove_placement_group(pg)


def test_slice_2d_mesh(monkeypatch):
    monkeypatch.setenv("TPU_TOPOLOGY", "2x4")
    ray_tpu.init(num_cpus=4, num_tpus=8, max_workers=4,
                 ignore_reinit_error=True)
    try:
        pg = placement_group([{"TPU": 4}], strategy="SLICE")
        assert pg.wait(10)
        (chips,) = _pg_entry(pg)["bundle_chips"]
        assert len(chips) == 4

        def coords(c):
            return (c % 2, c // 2)  # 2x4, x fastest (libtpu's numbering)

        assert _is_connected(chips, coords)
        remove_placement_group(pg)
    finally:
        ray_tpu.shutdown()


def test_slice_fragmented_fails(slice_cluster):
    # carve the 1x8 line into 0-1 / 2-5 / 6-7, free the ends, and ask
    # for 4 contiguous: {0,1,6,7} has no 4-path, so the PG must stay
    # pending (NOT silently spread across the gap)
    pg_a = placement_group([{"TPU": 2}], strategy="SLICE")
    assert pg_a.wait(10)
    pg_mid = placement_group([{"TPU": 4}], strategy="SLICE")
    assert pg_mid.wait(10)
    remove_placement_group(pg_a)
    import time

    time.sleep(0.2)  # removal is async; let the chips return
    pg_frag = placement_group([{"TPU": 4}], strategy="SLICE")
    assert not pg_frag.wait(2)
    # freeing the middle makes it feasible again
    remove_placement_group(pg_mid)
    assert pg_frag.wait(10)
    chips = _pg_entry(pg_frag)["bundle_chips"][0]
    assert _is_connected(chips, _coords_1x8)
    remove_placement_group(pg_frag)


def test_slice_rejected_without_topology(monkeypatch):
    monkeypatch.delenv("TPU_TOPOLOGY", raising=False)
    monkeypatch.delenv("TPU_CHIP_COORDS", raising=False)
    # 3 chips: no default topology => SLICE must be rejected loudly
    ray_tpu.init(num_cpus=2, num_tpus=3, max_workers=2,
                 ignore_reinit_error=True)
    try:
        with pytest.raises(ValueError, match="topology"):
            placement_group([{"TPU": 1}], strategy="SLICE")
    finally:
        ray_tpu.shutdown()


def test_slice_rejects_fractional_chips(slice_cluster):
    with pytest.raises(ValueError, match="whole TPU"):
        placement_group([{"TPU": 0.5}], strategy="SLICE")


def test_task_runs_on_reserved_chips(slice_cluster):
    pg = placement_group([{"TPU": 2}, {"TPU": 2}], strategy="SLICE")
    assert pg.wait(10)
    entry = _pg_entry(pg)

    @ray_tpu.remote(num_cpus=0, resources={"TPU": 2})
    def visible():
        return sorted(
            int(c) for c in os.environ["TPU_VISIBLE_CHIPS"].split(",")
        )

    for idx in (0, 1):
        got = ray_tpu.get(
            visible.options(
                scheduling_strategy=PlacementGroupSchedulingStrategy(
                    pg, placement_group_bundle_index=idx
                )
            ).remote(),
            timeout=60,
        )
        assert got == sorted(entry["bundle_chips"][idx])
    remove_placement_group(pg)


def test_get_current_placement_group(slice_cluster):
    assert get_current_placement_group() is None  # driver: not in a PG
    pg = placement_group([{"CPU": 1, "TPU": 1}], strategy="SLICE")
    assert pg.wait(10)

    @ray_tpu.remote(num_cpus=1, resources={"TPU": 1})
    def who():
        cur = get_current_placement_group()
        return None if cur is None else cur.id.hex()

    got = ray_tpu.get(
        who.options(
            scheduling_strategy=PlacementGroupSchedulingStrategy(pg, 0)
        ).remote(),
        timeout=60,
    )
    assert got == pg.id.hex()
    remove_placement_group(pg)


def test_whole_host_slice_task_spawns_worker(slice_cluster):
    """A SLICE PG reserving ALL chips empties the node free pool; tasks
    into its bundle must still trigger a worker spawn (chips come from
    the bundle, not the pool)."""
    pg = placement_group([{"TPU": 8}], strategy="SLICE")
    assert pg.wait(10)

    @ray_tpu.remote(num_cpus=0, resources={"TPU": 8})
    def visible():
        return sorted(
            int(c) for c in os.environ["TPU_VISIBLE_CHIPS"].split(",")
        )

    got = ray_tpu.get(
        visible.options(
            scheduling_strategy=PlacementGroupSchedulingStrategy(pg, 0)
        ).remote(),
        timeout=60,
    )
    assert got == list(range(8))
    remove_placement_group(pg)


def test_slice_chips_return_after_worker_death(slice_cluster):
    """PG-reserved chips survive their worker's death reserved (not
    leaked into the node free pool) and serve the next bundle task."""
    pg = placement_group([{"TPU": 2}], strategy="SLICE")
    assert pg.wait(10)

    @ray_tpu.remote(num_cpus=0, resources={"TPU": 2}, max_retries=0)
    def crash():
        os._exit(1)

    @ray_tpu.remote(num_cpus=0, resources={"TPU": 2})
    def visible():
        return sorted(
            int(c) for c in os.environ["TPU_VISIBLE_CHIPS"].split(",")
        )

    strat = PlacementGroupSchedulingStrategy(pg, 0)
    with pytest.raises(Exception):
        ray_tpu.get(crash.options(scheduling_strategy=strat).remote(),
                    timeout=60)
    got = ray_tpu.get(
        visible.options(scheduling_strategy=strat).remote(), timeout=60
    )
    assert got == sorted(_pg_entry(pg)["bundle_chips"][0])
    remove_placement_group(pg)


def test_slice_mixed_layout_fragmented_host(slice_cluster):
    """Mixed packing (case 3): several bundles share one host when the
    host's free chips are fragmented — no single path covers the whole
    gang (case 1) and there are fewer hosts than bundles (case 2).
    Layout: carve 1x8 into {0,1} {2,3} {4,5} {6,7} with holes at {2,3}
    and ask for three 2-chip bundles."""
    import time

    edge = placement_group([{"TPU": 2}], strategy="SLICE")
    assert edge.wait(10)
    hole = placement_group([{"TPU": 2}], strategy="SLICE")
    assert hole.wait(10)
    hole_chips = _pg_entry(hole)["bundle_chips"][0]
    assert len(hole_chips) == 2
    # free the edge allocation: the hole now sits MID-line, free chips
    # split into runs of 2 and 4 — no contiguous 6-path exists
    remove_placement_group(edge)
    time.sleep(0.2)

    pg = placement_group([{"TPU": 2}, {"TPU": 2}, {"TPU": 2}],
                         strategy="SLICE")
    assert pg.wait(10), "mixed packing must place 3x2 around the hole"
    entry = _pg_entry(pg)
    chips = entry["bundle_chips"]
    assert [len(c) for c in chips] == [2, 2, 2]
    flat = [c for chunk in chips for c in chunk]
    assert len(set(flat)) == 6 and not (set(flat) & set(hole_chips))
    for chunk in chips:
        assert _is_connected(chunk, _coords_1x8)
    remove_placement_group(pg)
    remove_placement_group(hole)
    time.sleep(0.2)


def test_slice_mixed_layout_prefers_per_host_ranks(slice_cluster):
    """When one bundle per host IS feasible it stays preferred; mixed
    packing only kicks in past it (here: single host, 2 bundles whose
    total fits contiguously -> case 1, adjacent chunks)."""
    pg = placement_group([{"TPU": 2}, {"TPU": 2}], strategy="SLICE")
    assert pg.wait(10)
    chips = _pg_entry(pg)["bundle_chips"]
    flat = [c for chunk in chips for c in chunk]
    assert _is_connected(flat, _coords_1x8)  # one contiguous 4-path
    remove_placement_group(pg)
