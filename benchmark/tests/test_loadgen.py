"""Both loops of the load generator against a handle that answers at once:
latency runs from the due time, lateness is kept, every token is timed."""

import time

from benchmark import loadgen
from benchmark.end_to_end import tpot_p90_ms, ttft_p90_ms
from benchmark.layer_metrics import gen_late_p99_ms
from benchmark.traffic import Offered


class Handle:
    """Streams ``max_tokens`` tokens 2 ms apart, then the finish reason."""

    def options(self, **_):
        return self

    def remote(self, body):
        for i in range(body["max_tokens"]):
            time.sleep(0.002)
            yield {"token": i}
        yield {"finish_reason": "length"}


def _offered(n, gap):
    return [Offered([1, 2, 3], 4, None if gap is None else i * gap, True)
            for i in range(n)]


def test_open_loop_times_from_the_due_time():
    started = []
    recs = loadgen.open_loop(Handle(), lambda ref, timeout: ref,
                             _offered(20, 0.01), 4, started.append)
    assert len(started) == 1 and len(recs) == 20 and all(r.ok for r in recs)
    assert [r.due for r in recs] == sorted(r.due for r in recs)
    assert all(r.sent >= r.due for r in recs)
    facts = {"requests": recs}
    assert 2.0 <= ttft_p90_ms.read(facts) < 100
    assert 2.0 <= tpot_p90_ms.read(facts) < 50
    assert 0 <= gen_late_p99_ms.read(facts) < 100


def test_closed_loop_keeps_its_clients_busy_until_the_window_closes():
    recs = loadgen.closed_loop(Handle(), lambda ref, timeout: ref,
                               _offered(2000, None), 3, 0.05, 0.2,
                               lambda t0: None)
    assert 10 < len(recs) < 2000 and all(r.due is None for r in recs)
    assert sum(r.ok for r in recs) >= len(recs) - 3
    assert gen_late_p99_ms.read({"requests": recs}) is None
