"""The step probe (builders/gpt2._StepProbe) and the slice's own counts
(builders/gpt2.slice_counts) on a decoder made by hand: a step is named
by what it carried, whatever method dispatched it."""
import contextlib

import pytest

from builders import gpt2
from harness import drives


class _Decoder:
    """Streams as {slot: [tokens held, prompt length]}; every step leaves
    the spans the program leaves (serving/paged.py), on a clock of its
    own that a step moves on by a millisecond."""
    page_tokens = 16

    def __init__(self, clock, spans):
        self.streams, self.clock, self.spans = {}, clock, spans
        self.moe = {'pairs': 0, 'experts_touched': 0, 'layer_calls': 0,
                    'decode.pairs': 0, 'decode.experts_touched': 0,
                    'decode.layer_calls': 0}

    def slot_tokens(self):
        return {s: held for s, (held, _) in self.streams.items()}

    def pool_stats(self):
        return {'pages_in_use': sum(-(-held // 16)
                                    for held, _ in self.streams.values())}

    def moe_counters(self):
        return dict(self.moe)

    def open_stream(self, slot, prompt):
        self.streams[slot] = [0, len(prompt)]

    def _span(self, name, **attrs):
        self.spans.append(dict(attrs, name=name, t0=self.clock[0],
                               t1=self.clock[0] + 1e-4))

    def _chunk(self, slot, rows):
        self._span(gpt2.PREFILL_TABLES, state_tokens=rows)
        self.streams[slot][0] += rows
        for k in ('pairs', 'experts_touched', 'layer_calls'):
            self.moe[k] += 3

    def _lanes(self):
        live = [s for s, (held, n) in self.streams.items() if held >= n]
        self._span(gpt2.DECODE_TABLES, state_lanes=len(live), pages_read=sum(
            self.streams[s][0] // 16 + 1 for s in live))
        for s in live:
            self.streams[s][0] += 1
        for k in ('pairs', 'experts_touched', 'layer_calls'):
            self.moe[k] += 2
            self.moe['decode.' + k] += 2

    def prefill_step(self, slot, rows=64):
        self._chunk(slot, min(rows, self.streams[slot][1]
                              - self.streams[slot][0]))
        self.clock[0] += 1e-3

    def decode_step(self, tokens=None, positions=None):
        self._lanes()
        self.clock[0] += 1e-3

    def mixed_step(self, slot, rows=64):
        """ROADMAP S14 (b): the lanes ride in the chunk's program."""
        self._lanes()
        self._chunk(slot, rows)
        self.clock[0] += 1e-3

    def _private_step(self):
        raise AssertionError('not a public callable: never wrapped')

    last_step = 7                       # not callable: never wrapped


@pytest.fixture()
def probed(monkeypatch):
    clock, spans, names = [100.0], [], []
    monkeypatch.setattr(gpt2.time, 'perf_counter', lambda: clock[0])
    monkeypatch.setattr(gpt2.spans, 'program_spans', lambda: list(spans))

    @contextlib.contextmanager
    def span(name):
        names.append(name)
        yield

    monkeypatch.setattr(gpt2.trace, 'span', span)
    dec = _Decoder(clock, spans)
    return dec, gpt2._StepProbe(dec, slice_s=4.0), names


def test_todays_two_methods_count_as_they_did(probed):
    dec, probe, names = probed
    dec.open_stream(0, range(100))
    dec.prefill_step(0)                 # 64 rows
    dec.prefill_step(0)                 # the last 36
    dec.decode_step()                   # one lane of 101 tokens
    dec.open_stream(1, range(10))
    dec.decode_step()                   # slot 1 stands inside its prompt
    dec.prefill_step(1)
    dec.decode_step()                   # two lanes: 103 + 11
    c = probe.counters()
    assert names == ['bench.prefill_step'] * 2 + ['bench.decode_step'] * 2 \
        + ['bench.prefill_step', 'bench.decode_step']
    assert (c['decode_calls'], c['prefill_calls']) == (3, 3)
    assert c['prefill_tokens'] == 110
    assert c['live_tokens'] == 101 + 102 + 103 + 11
    assert c['decode_s'] == pytest.approx(3e-3)
    assert c['prefill_s'] == pytest.approx(3e-3)
    assert c['kv_live_pages_max'] == 7 + 1 and c['kv_pages_in_use_max'] == 8
    # the slice: every step is plain, the program's attrs beside the probe's
    assert c['slice_decode_calls'] == c['slice_plain_decode_calls'] == 3
    assert c['slice_lanes'] == c['slice_state_lanes'] == 4
    assert c['slice_live_tokens'] == c['live_tokens']
    assert c['slice_pages_read'] == 7 + 7 + 7 + 1
    assert (c['slice_prefill_calls'], c['slice_state_tokens'],
            c['slice_prefill_tokens']) == (3, 110, 110)
    assert (c['moe_layer_calls'], c['moe_prefill_layer_calls']) == (6, 9)


def test_a_step_through_another_method_is_named_by_what_it_carried(probed):
    dec, probe, names = probed
    dec.open_stream(0, range(10))
    dec.prefill_step(0)
    dec.decode_step()                   # plain: one lane of 11
    dec.open_stream(1, range(100))
    dec.mixed_step(1)                   # the lane (12) and 64 rows
    dec.mixed_step(1, rows=36)          # the lane (13) and the last 36
    dec.decode_step()                   # plain: two lanes, 14 + 101
    c = probe.counters()
    assert names[-3:] == ['bench.mixed_step'] * 2 + ['bench.decode_step']
    assert (c['decode_calls'], c['prefill_calls']) == (4, 3)
    assert c['prefill_tokens'] == 110
    assert c['live_tokens'] == 11 + 12 + 13 + 14 + 101
    # a step with lanes is the engine's step; a chunk alone is prefill's
    assert c['decode_s'] == pytest.approx(4e-3)
    assert c['prefill_s'] == pytest.approx(1e-3)
    assert (c['slice_decode_calls'], c['slice_plain_decode_calls']) == (4, 2)
    assert (c['slice_live_tokens'], c['slice_plain_live_tokens']) == \
        (151, 11 + 115)
    assert (c['slice_state_lanes'], c['slice_plain_state_lanes']) == (5, 3)
    assert (c['slice_prefill_calls'], c['slice_state_tokens']) == (3, 110)


def test_the_slice_is_the_last_seconds_and_a_drive_takes_it_as_it_stands(
        probed):
    dec, probe, _ = probed
    dec.open_stream(0, range(10))
    dec.prefill_step(0)
    before = probe.counters()
    for _ in range(20):
        dec.decode_step()
    dec.clock[0] += 10.0                # far outside the slice's 4 s
    for _ in range(5):
        dec.decode_step()
    after = probe.counters()
    assert after['slice_decode_calls'] == 5 and after['decode_calls'] == 25
    assert after['slice_prefill_calls'] == 0
    # what the expert layers counted since the slice's first sample,
    # which the first of its five steps took
    assert after['slice_moe_layer_calls'] == 2 * 4
    assert after['slice_moe_prefill_layer_calls'] == 0
    assert len(probe.steps) == 5        # twice the slice is kept, no more
    both = dict(compiled_segments=0)
    c = drives._serve_counters(None, dict(before, **both),
                               dict(after, **both), 45.0)
    assert c['decode_calls'] == 25 and c['prefill_calls'] == 0
    assert c['slice_decode_calls'] == 5
    assert c['slice_live_tokens'] == after['slice_live_tokens']


def test_slice_counts_takes_a_steps_spans_by_when_they_began():
    steps = [(0.0, 1.0, 2, 40, 0), (1.0, 2.0, 2, 42, 64), (2.0, 3.0, 0, 0, 9)]
    spans = [{'name': gpt2.DECODE_TABLES, 't0': 0.1, 'latent_rows': 200},
             {'name': gpt2.DECODE_TABLES, 't0': 1.1, 'latent_rows': 210},
             {'name': gpt2.PREFILL_TABLES, 't0': 1.2},
             {'name': gpt2.PREFILL_TABLES, 't0': 2.5},
             {'name': 'exe.run', 't0': 2.6},
             # outside any step the probe saw: nobody's
             {'name': gpt2.DECODE_TABLES, 't0': 7.0, 'latent_rows': 999}]
    c = gpt2.slice_counts(steps, spans, since=0.5)
    assert (c['slice_decode_calls'], c['slice_latent_rows']) == (2, 410)
    assert (c['slice_plain_decode_calls'], c['slice_plain_latent_rows'],
            c['slice_plain_live_tokens']) == (1, 200, 40)
    assert (c['slice_prefill_calls'], c['slice_prefill_tokens']) == (2, 73)
    assert c['slice_state_tokens'] == 0
    late = gpt2.slice_counts(steps, spans, since=2.5)
    assert (late['slice_decode_calls'], late['slice_prefill_calls']) == (0, 1)
