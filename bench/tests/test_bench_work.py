"""The kernels' work counts are fixed numbers of the configurations'
shapes, and no tiling the autotuner may pick changes them."""
import pytest

from harness import experiments, roofline, spec
from harness import trace as trace_lib


@pytest.fixture(scope="module")
def shapes():
    bench = spec.load_benchmark()
    return {c["name"]: experiments.kernel_shape(spec.config(bench,
                                                            c["name"]))
            for c in bench["configs"]}


def test_trap40_untiled(shapes):
    w = spec.work_count("gen_untiled").work(shapes["trap40"])
    # 32 islands x (3 x 1024 x 160 int8 genes + 2 x 1024 f32 fitness)
    assert w["bytes"] == 32 * (3 * 1024 * 160 + 2 * 1024 * 4) == 15990784
    assert w["ops"] == 32 * 3 * 1024 * 160


def test_f15_tiled_and_eval(shapes):
    s = shapes["f15_d1000"]
    w = spec.work_count("gen_tiled").work(s)
    assert w["bytes"] == 3 * 10_000 * 1000 * 4 + 10_000 * 4 == 120_040_000
    assert w["ops"] == 7 * 10_000 * 1000
    e = spec.work_count("f15_eval").work(s)
    assert e["bytes"] == 10_000 * 1000 * 4 + 10_000 * 4 + 1000 * 50 * 4
    assert e["ops"] == 10_000 * 1000 * (2 * 50 + 5)


def test_work_does_not_depend_on_tiling(shapes):
    """Every tile size the autotuner may pick streams the population slice
    once per output tile, and does one-hot FLOPs on the padded shape; the
    count is the same for all of them and at most the least of them."""
    from repro.kernels.ga import autotune

    s = shapes["f15_d1000"]
    w = spec.work_count("gen_tiled").work(s)
    assert not {"tile_pop", "tile_len"} & set(s)
    pad = lambda x, t: -(-x // t) * t  # noqa: E731
    for tp, tl in autotune.CANDIDATES:
        n, L = pad(s["rows"], tp), pad(s["length"], tl)
        streamed = (n // tp) * n * L * 4 + 2 * n * L * 4
        onehot_flops = 2 * 3 * 2 * n * n * L
        assert w["bytes"] <= streamed
        assert w["ops"] <= onehot_flops


def _trace_with(name, durs, window=(0.0, 1.0)):
    ev = [trace_lib.Event(name, 0.01 * i, d) for i, d in enumerate(durs)]
    win = trace_lib.Event("window", window[0], window[1] - window[0])
    return trace_lib.Trace(device={0: ev}, host=[win])


def test_roofline_share_is_least_time_over_kernel_time(shapes):
    """Events that last exactly the least time read 100%; twice as long
    reads 50%. The share is never clipped."""
    s = shapes["f15_d1000"]
    pk = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    w = spec.work_count("gen_tiled").work(s)
    least = max(w["ops"] / pk["bf16_flops"],
                w["bytes"] / pk["hbm_bytes_per_s"])
    name = ('%closed_call.30 = f32[10240,1000]{1,0:T(8,128)} custom-call('
            'u32[1,2]{1,0:T(1,128)S(1)} %slice_bitcast_fusion.8, '
            'f32[10240,1000]{1,0:T(8,128)S(1)} %pad_bitcast_fusion.12), '
            'custom_call_target="tpu_custom_call"')
    for scale, want in ((1.0, 100.0), (2.0, 50.0), (0.5, 200.0)):
        tr = _trace_with(name, [least * scale] * 4)
        got = roofline.share({"trace": tr, "shape": s, "peaks": pk},
                             "gen_tiled")
        assert got == pytest.approx(want)
    assert roofline.share({"trace": _trace_with("other", [1e-3]),
                           "shape": s, "peaks": pk}, "gen_tiled") is None
