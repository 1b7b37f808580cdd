"""The closed loop of the closed-loop kinds: ``outstanding`` requests in
flight for the window, a new one sent as the oldest comes back."""
from __future__ import annotations

from bench.drivers import now


def run(send, outstanding: int, ledger, tracer, seconds: float
        ) -> tuple[float, float]:
    """Keep ``outstanding`` requests in flight for ``seconds``; ``send()``
    submits one and files its future in ``ledger``.  Returns the window's
    (start, end) on the host clock."""
    t0 = now()
    t_end = t0 + seconds
    tracer.start(t0)
    for _ in range(int(outstanding)):
        send()
    while ledger.pending:
        ledger.wait_oldest(t_end + 60.0)
        if now() < t_end:
            send()
    return t0, t_end
