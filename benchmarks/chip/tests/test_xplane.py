import xplane


def overlap(a, spans):
    """The plain loop that ``xplane.Cover`` replaces, kept as its reference."""
    return sum(max(0.0, min(a[1], s[1]) - max(a[0], s[0])) for s in spans)


def test_union_and_gaps():
    busy = xplane.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert xplane.total(busy) == 3.0
    assert xplane.gaps(busy, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                            (4.0, 5.0)]
    assert xplane.clip(busy, 1.0, 3.5) == [(1.0, 2.0), (3.0, 3.5)]
    assert overlap((2.0, 3.0), [(2.5, 6.0)]) == 0.5


def test_reduce_synthetic_nested_ops_are_a_union_not_a_sum():
    trace = {"devices": {"/device:TPU:0": [
        ("%while.1 = ...", 0.0, 1.0), ("%fusion.1 = f32[8] fusion(...)",
                                       0.2, 0.6), ("%copy", 2.0, 3.0)]},
        "modules": {"/device:TPU:0": [("jit_step(123)", 0.0, 1.0),
                                      ("jit_step(123)", 2.0, 3.0)]},
        "host": {"bench.window": [(0.0, 4.0)],
                 "bench.next_batch": [(1.0, 1.75)]}}
    red = xplane.reduce(trace, host_labels=("bench.next_batch",))
    assert red["busy_s"] == 2.0 and red["window_s"] == 4.0
    assert red["module_calls"] == {"jit_step": 2}
    assert red["module_seconds"]["jit_step"] == 2.0
    gaps = dict(map(tuple, red["idle_gaps"]))
    assert gaps["bench.next_batch"] == 0.75
    assert abs(gaps["(host: unlabelled)"] - 1.25) < 1e-12
    assert red["device_ops"][0][0].startswith("%while.1")


def test_recorded_v5e_trace(recorded_trace):
    """A 0.3 s window of fm24_train_text on a TPU v5 lite (chip run,
    PR 26): train steps and wire decodes alternate and fill the chip."""
    assert list(recorded_trace["devices"]) == ["/device:TPU:0"]
    red = xplane.reduce(recorded_trace, host_labels=(
        "bench.read_loss", "bench.next_batch", "bench.dispatch"))
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s"] / red["window_s"] > 0.9
    assert {"jit_step", "jit__unpack"} <= set(red["module_calls"])
    step_ms = 1e3 * red["module_seconds"]["jit_step"] / red[
        "module_calls"]["jit_step"]
    assert 20 < step_ms < 200
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) <= 10
    assert all(len(name) <= 96 for name, _ in red["device_ops"])


def test_cover_agrees_with_the_plain_overlap():
    spans = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (6.0, 6.5)]
    cover = xplane.Cover(spans)
    for gap in [(-1.0, 0.25), (0.25, 3.5), (2.0, 3.0), (3.9, 7.0),
                (6.5, 9.0), (1.0, 1.0)]:
        assert abs(cover.covered(gap)
                   - overlap(gap, xplane.union(spans))) < 1e-12
