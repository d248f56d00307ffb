"""``correct`` comes out false when the timed path is broken underneath.

Each test drives a whole run of the tiny cell (set-up, window, reference,
check) with the chip look skipped and one fault planted in the program:
a drain that returns its state unchanged, half of the batch left out with
the mean taken over the rest, and an answer altered where it is produced
(a lane's BT in the drain, a payload word in packetize). No cell of the
benchmark spans chips, so there is no exchange between chips to leave out.
"""
import dataclasses

import numpy as np
import pytest

import run as bench_run


def _run(tiny):
    import jax
    config, traffic, layers = tiny
    return bench_run.run_cell("tiny", config, traffic, 7, 0.5, False,
                              jax.devices(), layers=layers)


def _patch_drain(monkeypatch, fault):
    import repro.noc.sweep as sweep
    real = sweep.simulate_batch

    def broken(cfg, traffic, **kw):
        return fault(real, cfg, traffic, **kw)

    monkeypatch.setattr(sweep, "simulate_batch", broken)


def test_sound_run_is_correct(tiny):
    assert _run(tiny)["correct"]


def test_state_returned_unchanged(monkeypatch, tiny):
    def unchanged(real, cfg, traffic, **kw):
        out = real(cfg, traffic, **kw)
        return [dataclasses.replace(
            r, cycles=0, ejected=0, link_bt=np.zeros_like(r.link_bt),
            link_flits=np.zeros_like(r.link_flits),
            inj_bt=np.zeros_like(r.inj_bt), total_bt=0, inter_router_bt=0,
            drain_cycle=0) for r in out]

    _patch_drain(monkeypatch, unchanged)
    # The sweep divides by the baseline lane's BT, which is now 0: the
    # run ends without a result, which counts as a failed run.
    with pytest.raises(ZeroDivisionError):
        _run(tiny)


def test_state_unchanged_after_set_up(monkeypatch, tiny):
    """The same fault planted once set-up is done: no row comes, and the
    run reports itself not correct."""
    real_window = bench_run.window

    def window(*a, **kw):
        import repro.noc.sweep as sweep
        monkeypatch.setattr(sweep, "simulate_batch",
                            lambda *a_, **k_: (_ for _ in ()).throw(
                                RuntimeError("drain returned nothing")))
        return real_window(*a, **kw)

    monkeypatch.setattr(bench_run, "window", window)
    res = _run(tiny)
    assert not res["correct"]
    assert res["check"]["rows_missing"]["value"] == 3


def test_half_the_batch_left_out(monkeypatch, tiny):
    def half(real, cfg, traffic, **kw):
        b = int(traffic.length.shape[0])
        keep = max(b // 2, 1)
        sub = traffic._replace(**{f: getattr(traffic, f)[:keep] for f in
                                  ("words", "dest", "meta", "vc", "pkt",
                                   "length")})
        if kw.get("mc_nodes") is not None:
            kw["mc_nodes"] = np.asarray(kw["mc_nodes"])[:keep]
        out = real(cfg, sub, **kw)
        mean = int(np.mean([r.total_bt for r in out]))
        return out + [dataclasses.replace(out[-1], total_bt=mean)
                      for _ in range(b - keep)]

    _patch_drain(monkeypatch, half)
    res = _run(tiny)
    assert not res["correct"]
    assert res["check"]["bt_rows_differing"]["value"] > 0


def test_answer_altered_in_the_drain(monkeypatch, tiny):
    def altered(real, cfg, traffic, **kw):
        out = real(cfg, traffic, **kw)
        out[-1] = dataclasses.replace(out[-1], total_bt=out[-1].total_bt + 1)
        return out

    _patch_drain(monkeypatch, altered)
    res = _run(tiny)
    assert not res["correct"]
    assert res["check"]["bt_max_abs_diff"]["value"] == 1


def test_word_altered_in_packetize(monkeypatch, tiny):
    import repro.noc.sweep as sweep
    real = sweep.build_traffic_streamed_multi

    def altered(*a, **kw):
        out = real(*a, **kw)
        t = out[0]
        words = np.array(t.words)
        # High bits no byte-valued neighbour has: every link it crosses
        # counts 16 more transitions on each side.
        words[-1, 0, 2, 3] ^= np.uint32(0xFFFF0000)
        out[0] = t._replace(words=words)
        return out

    monkeypatch.setattr(sweep, "build_traffic_streamed_multi", altered)
    res = _run(tiny)
    assert not res["correct"]
    assert res["check"]["bt_rows_differing"]["value"] >= 1
