"""The end-to-end and per-layer readers on made-up windows: rates span
the whole window, shares of a peak stay silent off the chip."""
from types import SimpleNamespace

import pytest

from chipbench import harness


def req(n=0):
    return dict(n_tokens=n)


def ctx(requests, t0=0.0, t1=10.0, **kw):
    return SimpleNamespace(requests=requests, t0=t0, t1=t1,
                           window_s=t1 - t0, **kw)


def read(name, c):
    return harness.reader(name)(c)


def test_output_rate_spans_the_whole_window():
    rs = [req(n=50), req(n=150)]
    assert read("output_tok_s", ctx(rs, t0=5.0, t1=9.0)) == pytest.approx(50.0)


def test_prefill_share_and_setup():
    steps = [dict(T=8)] * 3 + [dict(T=1)] * 9
    c = ctx([], steps=steps, setup_s=12.5)
    assert read("prefill_step_share", c) == pytest.approx(25.0)
    assert read("setup_s", c) == 12.5
    assert read("prefill_step_share", ctx([], steps=[])) is None


@pytest.mark.parametrize("name", ["device_idle_pct.decode",
                                  "device_idle_pct.chat"])
def test_idle_share(name):
    c = ctx([], trace=SimpleNamespace(busy_s=1.5, window_s=2.0))
    assert read(name, c) == pytest.approx(25.0)
    assert read(name, ctx([], trace=None)) is None


@pytest.mark.parametrize("name", ["step_mfu", "dequant_matmul_roofline",
                                  "decode_attention_roofline"])
def test_share_readers_stay_silent_without_a_chip(name):
    c = ctx([], steps=[dict(T=1, valid=4, rows=40, qrows=40, slots=4)],
            trace=None, peaks=None)
    assert read(name, c) is None
