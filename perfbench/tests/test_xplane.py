"""The xplane reduction on a synthetic trace: busy union, idle share,
self times, per-program device time, gaps attributed to host
annotations, collective time that compute does not cover."""
import pytest

from perfbench.lib import xplane
from perfbench.lib.xplane import DeviceTrace, Trace

US = 1e-6


def ev(name, start_us, dur_us):
    return (name, start_us * US, (start_us + dur_us) * US)


def device(ops, modules=(), name="/device:TPU:0"):
    order = lambda e: (e[1], -e[2])  # noqa: E731
    return DeviceTrace(name, sorted(ops, key=order), sorted(modules, key=order))


def test_union_clip_subtract():
    merged = xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert merged == [(0, 3), (5, 8)]
    assert xplane.total(merged) == 6
    assert xplane.clip(merged, 2, 6) == [(2, 3), (5, 6)]
    assert xplane.subtract([(0, 10)], merged) == [(3, 5), (8, 10)]
    assert xplane.subtract([(0, 3), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert xplane.subtract([(0, 3)], []) == [(0, 3)]


def test_busy_and_idle_share_average_over_devices():
    d0 = device([ev("fusion.1", 0, 40), ev("copy.2", 30, 30),
                 ev("fusion.3", 80, 20)])          # busy 0-60, 80-100
    d1 = device([ev("fusion.1", 0, 100)], name="/device:TPU:1")
    trace = Trace([d0, d1], {})
    window = xplane.window_of(trace)
    assert window == pytest.approx((0.0, 100 * US))
    assert xplane.busy_seconds(trace, window) == pytest.approx(90 * US)
    assert xplane.idle_share(trace, window) == pytest.approx(0.10)


def test_self_times_and_leaves_see_through_a_while():
    ops = device([ev("while.9", 0, 100), ev("fusion.1", 10, 30),
                  ev("all-reduce-done.4", 40, 20), ev("fusion.2", 70, 10),
                  ev("copy.7", 120, 5)]).ops
    own = xplane.self_times(ops)
    assert own["while.9"] == pytest.approx(40 * US)
    assert own["fusion.1"] == pytest.approx(30 * US)
    assert own["copy.7"] == pytest.approx(5 * US)
    assert [n for n, _, _ in xplane.leaves(ops)] == [
        "fusion.1", "all-reduce-done.4", "fusion.2", "copy.7"]
    trace = Trace([device(ops)], {})
    top = xplane.top_ops(trace, (0.0, 125 * US), n=2)
    assert [n for n, _ in top] == ["while.9", "fusion.1"]


def test_exposed_collective_time_is_what_compute_leaves_bare():
    """A core runs its operations one after another: an asynchronous
    collective shows as a short ``-start`` and a ``-done`` that lasts as
    long as the core waits, both inside the layer loop's ``while``."""
    d = device([ev("while.9", 0, 100),
                ev("fusion.1", 0, 30),
                ev("all-gather-start.2", 30, 2),
                ev("fusion.3", 32, 28),              # hides the transfer
                ev("all-gather-done.2", 60, 15),     # what it did not hide
                ev("all-reduce.5", 75, 20)])         # synchronous
    trace = Trace([d], {})
    assert xplane.collective_exposed_seconds(
        trace, (0.0, 100 * US)) == pytest.approx(37 * US)
    # clipped to the window
    assert xplane.collective_exposed_seconds(
        trace, (0.0, 70 * US)) == pytest.approx(12 * US)
    assert xplane.name_seconds(
        trace, (0.0, 100 * US), r"^fusion") == pytest.approx(58 * US)


def test_program_time_and_host_gaps():
    ops = [ev("fusion.a", 0, 48), ev("fusion.b", 50, 50),        # decode 1
           ev("fusion.c", 110, 40),                              # chunk
           ev("fusion.a", 152, 48), ev("fusion.b", 200, 50),     # decode 2
           ev("fusion.a", 260, 100)]                             # decode 3
    modules = [ev("jit__decode_fn(1)", 0, 100),
               ev("jit__prefill_chunk_fn(2)", 110, 40),
               ev("jit__decode_fn(1)", 152, 98),
               ev("jit__decode_fn(1)", 260, 100)]
    d = device(ops, modules)
    window = (0.0, 360 * US)
    busy = xplane.module_busy_seconds(d, r"jit__decode_fn", window)
    assert busy == pytest.approx([98 * US, 98 * US, 100 * US])
    assert xplane.module_busy_seconds(
        d, r"jit__prefill_chunk_fn", window) == pytest.approx([40 * US])
    # after decode 1 the chunk starts 10 us later; after decode 2, 10 us;
    # decode 3 has no successor inside the window
    steps = r"jit__decode_fn|jit__prefill_chunk_fn"
    assert xplane.gaps_after(d, r"jit__decode_fn", window,
                             then=steps) == pytest.approx([10 * US, 10 * US])
    # a small program of the host loop's own does not end the gap
    d.modules.insert(1, ev("jit_broadcast_in_dim(7)", 103, 2))
    assert xplane.gaps_after(d, r"jit__decode_fn", window,
                             then=steps) == pytest.approx([10 * US, 10 * US])
    assert xplane.gaps_after(d, r"jit__prefill_chunk_fn", window) == []


def test_idle_gaps_go_to_the_annotation_and_call_that_cover_them():
    d = device([ev("fusion.a", 0, 100), ev("fusion.a", 160, 100),
                ev("fusion.a", 300, 100)])
    host = {
        "python3": sorted([
            ev("serve", 90, 200),                    # step annotation
            ev("serve_decode", 95, 100),
            ev("DevicePutWithSharding", 110, 45),    # covers mid of gap 1
            ev("$server.py:1816 _decode_step", 96, 90),   # python frame: no
            ev("np.asarray(jax.Array)", 270, 25),    # covers mid of gap 2
        ], key=lambda e: (e[1], -e[2])),
        "other-thread": [ev("noise", 0, 400)],
    }
    trace = Trace([d], host)
    annotations = ("serve", "serve_decode", "serve_prefill_chunk")
    got = dict(xplane.attribute_gaps(trace, (0.0, 400 * US), annotations))
    assert got == pytest.approx({
        "serve_decode___DevicePutWithSharding": 60 * US,
        "serve___np.asarray_jax.Array": 40 * US})


def test_loading_a_recorded_cpu_trace_finds_no_device(tmp_path):
    """The loader reads a real file with nothing but JAX; a CPU trace has
    no TPU plane, which the harness refuses as 'no device operation'."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("serve_decode"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = xplane.find_xplane(str(tmp_path))
    assert path is not None
    trace = xplane.load(path)
    assert trace.devices == []
    assert any(nm == "serve_decode" for evs in trace.host.values()
               for nm, _, _ in evs)
    with pytest.raises(ValueError):
        xplane.window_of(trace)


def test_short_names_of_hlo_instructions():
    text = ("%fusion.5 = pred[768000]{0:T(1024)(128)(4,1)} fusion(f32[24,32000]"
            "{1,0:T(8,128)} %p), kind=kLoop")
    assert xplane.short_name(text) == "fusion.5 pred[768000]"
    assert xplane.op_label(xplane.short_name(text)) == "fusion.5_pred_768000"
    call = ('%custom-call.3 = (bf16[2,32,2048,128]{3,2,1,0}, f32[2,32,2048])'
            ' custom-call(%a, %b), custom_call_target="tpu_custom_call"')
    assert xplane.short_name(call) == (
        "custom-call.3 bf16[2,32,2048,128] tpu_custom_call")
    assert xplane.short_name("%while.107 = (s32[]{:T(128)}, bf16[2,4]) "
                             "while(...)") == "while.107 s32[]"
    assert xplane.short_name("jit__train_step(466)") == "jit__train_step(466)"
    assert xplane.COLLECTIVE.match(xplane.short_name(
        "%all-gather-done.2 = bf16[4096,1024]{1,0} all-gather-done(%x)"))
