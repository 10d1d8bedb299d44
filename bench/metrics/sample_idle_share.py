"""Share of the timer's samples in which no operation ran on the device, in
%: 1 - device busy time inside the program's ``session.sample`` spans over
their merged length, in the traced window. What is not kernel time there is
host dispatch and synchronisation.

Reading the program's spans also prints the window's idle time by the
innermost program span and device seconds by program on stderr."""

from bench.harness import say
from bench.program_trace import of_run


def read(window):
    spans = of_run(window.trace)
    if spans is None or not spans.spans:
        return None
    idle = sum(spans.idle_by_span.values()) or 1.0
    say("idle by program span: " + ", ".join(
        f"{name} {s:.3f} s ({100.0 * s / idle:.1f}%)" for name, s in spans.top_idle(20)))
    say("device seconds by program: " + ", ".join(
        f"{name} {s:.3f} s" for name, s in spans.top_programs(20)))
    share = spans.idle_share("session.sample")
    return None if share is None else 100.0 * share
