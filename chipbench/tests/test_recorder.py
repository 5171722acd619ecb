import pytest

from chipbench.recorder import Recorder


def _steady(stall_at=None, stall=0.0, opened=0.0):
    """Four requests due at 0, 0.5, 1, 1.5 s; each gets a first token
    0.1 s after it is due and 9 more in chunks of 3, 0.2 s apart. A stall
    delays every stamp after ``stall_at``."""
    rec = Recorder()
    rec.opened = opened
    for i in range(4):
        uid, due = i, 0.5 * i
        rec.submitted(uid, 0, due)
        rec.expect(uid, 10)
        stamps = [due + 0.1] + [due + 0.1 + 0.2 * (1 + j // 3)
                                for j in range(9)]
        for t in stamps:
            if stall_at is not None and t > stall_at:
                t += stall
            rec.tokens(uid, 1, t)
    # every distinct stamp ends a pass of the serve loop
    rec.marks = sorted({t for r in rec.recs.values() for t in r.times})
    return rec


def test_arithmetic():
    rec = _steady()
    # tokens stamped in (0, 1]: request 0 all 10 (last at 0.7), request 1
    # (due 0.5): 0.6, 0.8 x3, 1.0 x3 -> 7
    assert rec.tokens_in(0.0, 1.0) == 17
    # the close at 1.0 s falls on request 1's chunk: the span ends there
    assert rec.span_end(1.0) == pytest.approx(1.0)
    assert rec.decode_tok_s(1.0) == pytest.approx(17.0)
    # a close at 0.9 s: the span runs on to the first stamp after it
    # (1.0 s), so no second of the window goes uncounted
    assert rec.span_end(0.9) == pytest.approx(1.0)
    assert rec.decode_tok_s(0.9) == pytest.approx(17.0)
    # past the last stamp there is no end yet
    assert rec.span_end(5.0) is None and rec.decode_tok_s(5.0) is None


def test_the_window_counts_from_its_open():
    rec = _steady(opened=0.5)
    # (0.5, 1.0]: request 1's 7 tokens and request 0's none (its last
    # stamp is 0.7: 0.7 x3 -> 3)
    assert rec.tokens_in(0.5, 1.0) == 10
    assert rec.span_end(0.5) == pytest.approx(1.0)
    assert rec.decode_tok_s(0.5) == pytest.approx(10 / 0.5)


def test_a_stall_worsens_the_rate_and_the_tails():
    base, stalled = _steady(), _steady(stall_at=0.55, stall=0.5)
    assert stalled.decode_tok_s(1.0) < base.decode_tok_s(1.0)


@pytest.mark.parametrize("close", [0.95, 1.0, 1.05])
def test_a_stall_after_the_last_stamp_in_the_window_counts(close):
    """A stall of 2 s right after the stamp at 0.9 s, the last before a
    close at ``close`` in the stalled run, pushes the next chunk far past
    the close: the span runs on to it, and the rate falls by the stall
    (a rate taken to the last stamp inside the window would barely move:
    14 tokens over 0.9 s)."""
    base, stalled = _steady(), _steady(stall_at=0.9, stall=2.0)
    assert stalled.span_end(close) == pytest.approx(3.0)
    assert stalled.decode_tok_s(close) < 0.4 * base.decode_tok_s(close)


def test_a_chunk_stamped_row_by_row_counts_whole():
    """The loop stamps a chunk's rows one after another; the close falls
    between the first row's stamp and the last. The span ends with the
    pass, so all rows of that chunk count, not the first alone."""
    rec = Recorder()
    for uid in range(3):
        rec.submitted(uid, 0, 0.0)
        rec.tokens(uid, 1, 0.1 + uid * 1e-4)  # admission pass
    rec.mark()
    rec.open()
    for step in range(1, 4):  # three chunks of 4 tokens a row
        for uid in range(3):
            rec.tokens(uid, 4, 0.1 + step + uid * 1e-4)
        rec.mark()
    close = 3.0 + 1e-4 - rec.opened  # just after row 0's stamp of chunk 3
    end = rec.span_end(close)
    assert end == pytest.approx(3.1002)
    assert rec.tokens_in(rec.opened, end) == 36
    assert rec.decode_tok_s(close) == pytest.approx(36 / (end - rec.opened))


def test_preemption_keeps_the_first_token():
    rec = Recorder()
    rec.submitted(1, 0, 0.0)
    rec.tokens(1, 3, 0.5)
    rec.preempted(1)
    rec.submitted(1, 0, 0.9)  # requeued: its due time stays
    assert rec.recs[1].t_due == 0.0 and rec.recs[1].times == [0.5]
