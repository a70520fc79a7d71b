"""``loop_send_trace_pct``: the share of the traced window the gateway's
event-loop thread spent under ``dstpu/gateway/send``, on the profiler's
clock. The program puts ONE SSE token event's send in 64 under that span
(``serving/gateway.py: _respond_stream``, ``SEND_SPAN_EVERY``: the ``write``
that hands the event's bytes to the transport; made only while the sink is
on; a span an event cost a traced run 8% of its tokens, PR 52), so this is a
sixty-fourth of the time the loop spends sending, and it moves as that does:
with the number of sends and with what a send takes. It stands beside
``pump_wait_trace_pct`` and the device's operations in the same capture. The
program's own account of the same thread is ``loop_cpu_ms`` (its processor
time a sync) and ``delivery_lag_ms``. A program without the span (the parent
of PR 52) gives nothing."""

from chipbench import trace_reduce, xplane

SEND_SPAN = "dstpu/gateway/send"


def reduce(obs):
    trace = xplane.run_trace(obs)
    if trace is None:
        return None
    t0, t1 = trace["t0"], trace["t1"]
    sends = [(s, s + d) for n, s, d in trace_reduce.clip(trace["host"], t0, t1)
             if n == SEND_SPAN]
    if not sends:
        return None
    return 100.0 * trace_reduce.total(trace_reduce.union(sends)) / (t1 - t0)
