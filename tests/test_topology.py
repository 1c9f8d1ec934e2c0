"""TPU slice topology + slice-aware gang scheduling.

Exceeds the reference's TPU support (ref: _private/accelerators/tpu.py —
custom resources + pod-name affinity only): the scheduler here reasons
about host grids and ICI adjacency directly.
"""

import pytest

from ray_tpu.runtime.topology import (TpuHost, TpuSlice, detect_host_tpu,
                                      slice_from_nodes, virtual_slice)


def test_virtual_v5e_64_shape():
    s = virtual_slice("v5e-64")
    assert s.chip_topology == (8, 8)
    assert s.host_grid == (4, 4)
    assert len(s.hosts) == 16
    assert s.num_chips == 64
    assert all(h.chips == 4 for h in s.hosts)


def test_ici_neighbors_torus():
    s = virtual_slice("v5e-64")
    corner = s.host_at((0, 0))
    names = {n.coords for n in s.ici_neighbors(corner)}
    # 4x4 host grid closes into a torus on both axes
    assert names == {(1, 0), (0, 1), (3, 0), (0, 3)}


def test_contiguous_hosts_compact_rectangles():
    s = virtual_slice("v5e-64")
    gang = s.contiguous_hosts(4)
    coords = sorted(h.coords for h in gang)
    # most compact shape for 4 hosts is 2x2, not 1x4
    xs = {c[0] for c in coords}
    ys = {c[1] for c in coords}
    assert len(xs) == 2 and len(ys) == 2
    # 8 hosts -> 2x4 (perimeter 6) over 1x8 (doesn't fit 4x4 anyway)
    gang8 = s.contiguous_hosts(8)
    assert len(gang8) == 8
    xs = sorted({h.coords[0] for h in gang8})
    ys = sorted({h.coords[1] for h in gang8})
    assert (len(xs), len(ys)) in ((2, 4), (4, 2))
    # whole slice
    assert len(s.contiguous_hosts(16)) == 16
    assert s.contiguous_hosts(17) is None


def test_contiguous_hosts_partial_slice():
    """Holes in the grid (hosts down) force a different placement."""
    s = virtual_slice("v5e-64")
    # remove the (0,0) 2x2 corner block's host
    s.hosts = [h for h in s.hosts if h.coords != (0, 0)]
    gang = s.contiguous_hosts(4)
    assert gang is not None
    assert (0, 0) not in {h.coords for h in gang}


def test_detect_host_tpu_env(monkeypatch):
    # a TPU host may preset TPU_* in the environment; isolate them
    monkeypatch.delenv("TPU_TOPOLOGY", raising=False)
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5e-16")
    monkeypatch.setenv("TPU_NAME", "my-pod")
    monkeypatch.setenv("TPU_WORKER_ID", "2")
    labels = detect_host_tpu()
    assert labels["rtpu.slice"] == "my-pod"
    assert labels["rtpu.worker_index"] == "2"
    assert labels["rtpu.topology"] == "4x4"
    monkeypatch.delenv("TPU_ACCELERATOR_TYPE")
    assert detect_host_tpu() == {}


class _FakeNode:
    def __init__(self, node_id, labels, tpus=4.0):
        self.node_id = node_id
        self.labels = labels
        self.total_resources = {"TPU": tpus, "CPU": 8.0}
        self.available_resources = dict(self.total_resources)
        self.alive = True


def _fake_slice_nodes(n=16, slice_name="pod-a", accel="v5e-64"):
    from ray_tpu.runtime.topology import _default_topology

    topo = "x".join(str(t) for t in _default_topology(accel))
    return [
        _FakeNode(f"{slice_name}-n{i}", {
            "rtpu.slice": slice_name, "rtpu.tpu_type": accel,
            "rtpu.worker_index": str(i), "rtpu.topology": topo,
        }) for i in range(n)
    ]


def test_slice_from_nodes():
    slices = slice_from_nodes(_fake_slice_nodes())
    assert set(slices) == {"pod-a"}
    s = slices["pod-a"]
    assert s.host_grid == (4, 4)
    assert len(s.hosts) == 16
    # worker 5 of a 4x4 grid sits at (1, 1) row-major
    assert s.host_at((1, 1)).worker_index == 5


def test_slice_pack_place_bundles():
    """SLICE_PACK places one bundle per host on ICI-adjacent hosts of a
    single slice (the TPU-native placement group)."""
    from ray_tpu.runtime.scheduling import place_bundles

    nodes = _fake_slice_nodes() + [
        _FakeNode("cpuonly", {}),  # no slice: never eligible
    ]
    bundles = [{"TPU": 4.0}] * 4
    placement = place_bundles(nodes, bundles, "SLICE_PACK")
    assert placement is not None and len(placement) == 4
    assert "cpuonly" not in placement
    by_id = {n.node_id: n for n in nodes}
    coords = sorted(
        slice_from_nodes([by_id[p] for p in placement])["pod-a"].host_at
        is not None for p in placement)
    # all four on one slice, 2x2 block
    chosen = [by_id[p] for p in placement]
    widx = sorted(int(n.labels["rtpu.worker_index"]) for n in chosen)
    rows = {i // 4 for i in widx}
    cols = {i % 4 for i in widx}
    assert len(rows) == 2 and len(cols) == 2


def test_slice_pack_insufficient_resources():
    from ray_tpu.runtime.scheduling import place_bundles

    nodes = _fake_slice_nodes(4, accel="v5e-16")
    for n in nodes:
        n.available_resources["TPU"] = 0.0  # busy
    assert place_bundles(nodes, [{"TPU": 4.0}] * 2, "SLICE_PACK") is None


def test_slice_pack_spans_not_slices():
    """Two half-free slices: the gang must land in ONE of them."""
    from ray_tpu.runtime.scheduling import place_bundles

    a = _fake_slice_nodes(4, "pod-a", "v5e-16")
    b = _fake_slice_nodes(4, "pod-b", "v5e-16")
    placement = place_bundles(a + b, [{"TPU": 4.0}] * 4, "SLICE_PACK")
    assert placement is not None
    chosen = {p for p in placement}
    in_a = sum(1 for n in a if n.node_id in chosen)
    in_b = sum(1 for n in b if n.node_id in chosen)
    assert (in_a, in_b) in ((4, 0), (0, 4))
