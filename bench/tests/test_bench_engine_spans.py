"""The four readers of the engine's own spans and counters
(`host.decode_gap_ms`, `sched.refill_ms_per_ktok`, `setup.compile_s`,
`host.gc_pause_ms`) on a recorder filled by hand through the program's
`Telemetry`, against values worked out by hand; and nothing from a
recorder with nothing in it, or from a program that records no spans."""
import types

import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import cell

from repro.serve import Telemetry


class Clock:
    t = 0.0

    def __call__(self):
        return self.t


def decode_step(tel, clk, i, t0, dispatch_end, sync_end, end, admitted=(),
                active=2, gc=None):
    """One engine step as the engine spans it: the root, then dispatch
    [t0, dispatch_end], sync [dispatch_end, sync_end] (optionally holding
    a generation-2 collection `gc` = (start, end)), emit [sync_end, end]."""
    clk.t = t0
    with tel.span("engine.step", i) as root:
        with tel.span("engine.decode.dispatch", i):
            clk.t = dispatch_end
        with tel.span("engine.decode.sync", i):
            if gc is not None:
                clk.t = gc[0]
                tel._gc_start()
                clk.t = gc[1]
                tel._gc_stop()
            clk.t = sync_end
        with tel.span("engine.decode.emit", i):
            clk.t = end
        root["attrs"].update(mode="decode", active=active,
                             admitted=list(admitted), prompt_tokens=0)


def recorded():
    """Two slots.  Step 0 fills (admits uids 0 and 1, not of the window);
    step 3 admits window request 5 (100 prompt tokens) and takes 0.5 s
    more than a plain step; step 5 decodes one slot only.  A 250-ms
    collection inside step 1 and a 100-ms one inside step 5."""
    clk = Clock()
    tel = Telemetry(clock=clk)
    tel.n_slots = 2
    decode_step(tel, clk, 0, 0.000, 0.010, 0.400, 0.402, admitted=(0, 1))
    decode_step(tel, clk, 1, 0.405, 0.406, 0.810, 0.812, gc=(0.5, 0.75))
    decode_step(tel, clk, 2, 0.815, 0.818, 1.220, 1.222)
    decode_step(tel, clk, 3, 1.225, 1.725, 2.130, 2.132, admitted=(5,))
    decode_step(tel, clk, 4, 2.135, 2.139, 2.540, 2.545)
    decode_step(tel, clk, 5, 2.550, 2.551, 2.950, 2.952, active=1,
                gc=(2.6, 2.7))
    tel.on_compile("jit(_decode_fn)", 1.0, 0.5, 10.0)
    tel.on_compile("jit(_prefill_fn)", 2.0, 1.0, 20.0)
    window = [types.SimpleNamespace(uid=5, prompt=[1] * 100),
              types.SimpleNamespace(uid=6, prompt=[1] * 40)]   # not admitted
    return types.SimpleNamespace(telemetry=tel, window_recs=window)


# decode gaps of the consecutive full-slot pairs (0,1) (1,2) (2,3) (3,4):
# 406-400, 818-810, 1725-1220, 2139-2130 ms -> median of 6, 8, 505, 9.
# Plain full-slot walls: 0.407, 0.407, 0.410 -> median 0.407; the refill
# step's wall 0.907 is 0.5 s over it, for 100 window prompt tokens.
# The collection inside step 5 starts after the last full-slot step ends.
EXPECTED = {"host.decode_gap_ms": 8.5,
            "sched.refill_ms_per_ktok": 5000.0,
            "setup.compile_s": 34.5,
            "host.gc_pause_ms": 250.0}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_recorder_filled_by_hand(name):
    got = cell.metric_reader(name).read(recorded())
    assert got == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_in_an_empty_recorder(name):
    reader = cell.metric_reader(name)
    tel = Telemetry()
    tel.n_slots = 2
    assert reader.read(types.SimpleNamespace(telemetry=tel,
                                             window_recs=[])) is None
    # a program whose recorder has no spans or counters at all
    bare = types.SimpleNamespace(events=[], records={}, n_slots=2)
    assert reader.read(types.SimpleNamespace(telemetry=bare,
                                             window_recs=[])) is None


def test_gc_pause_reads_zero_when_no_collection_fell_in_the_steps():
    ctx = recorded()
    ctx.telemetry.gc_pauses.clear()
    assert cell.metric_reader("host.gc_pause_ms").read(ctx) == 0.0


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_is_listed_for_the_batch_cell(name):
    entry = {m["name"]: m for m in cell.load_spec()["per_layer"]}[name]
    assert entry["workloads"] == ["glm4_9b.batch"]
    assert entry["source"] in ("program_span", "program_counter")


def test_stalls_in_the_window():
    """bench/run.py's stall log: the longest `engine.step` inside the
    window, its longest phase, and the steps over 5x the median."""
    from bench import run

    clk = Clock()
    tel = Telemetry(clock=clk)
    tel.attach(n_slots=2, clock=clk)
    t = 0.0
    # a refill (step 2, admitting uid 9) takes 2.0 s, a stall 1.5 s
    for i, sync in enumerate([0.1] * 2 + [2.0] + [0.1] * 5 + [1.5, 0.1]):
        decode_step(tel, clk, i, t, t + 0.01, t + 0.01 + sync,
                    t + 0.02 + sync, admitted=[9] if i == 2 else ())
        t += 0.02 + sync
    got = run.stalls(tel, 0.0, t)
    assert got == {"max_step_ms": "2020.000",
                   "max_step_phase": "engine.decode.sync",
                   "max_decode_step_ms": "1520.000",
                   "median_decode_step_ms": "120.000",
                   "slow_decode_steps": 1}
    assert run.stalls(tel, t + 1, t + 2) == {"max_step_ms": "na"}
