"""The one traffic generator: a closed loop read from a traffic file.

A traffic mix is a JSON file under ``bench/traffic/`` with these keys:

* ``loop``: ``"closed"`` — one client; the next call starts when the last
  one has ended in ``block_until_ready`` (callers that wait for each result,
  as an SCF loop or a chain of products does).
* ``calls_per_sample``: calls timed together as one latency sample, so that
  a sample spans well over the host clock's jitter where one call is short.
* ``warmup_calls``: calls made in set-up, before the window, so every shape
  the window uses is compiled and every plan is cached.
"""

from __future__ import annotations

import time

KEYS = {"loop", "calls_per_sample", "warmup_calls", "why"}


def validate(traffic: dict) -> dict:
    unknown = set(traffic) - KEYS
    if unknown or traffic.get("loop") != "closed":
        raise ValueError(f"traffic: unknown keys {sorted(unknown)} or loop "
                         f"{traffic.get('loop')!r} is not 'closed'")
    for k in ("calls_per_sample", "warmup_calls"):
        if not isinstance(traffic[k], int) or traffic[k] < 1:
            raise ValueError(f"traffic: {k} must be a whole number >= 1")
    return traffic


def warm_up(call, traffic: dict) -> None:
    for _ in range(traffic["warmup_calls"]):
        call()


def closed_loop(call, seconds: float, traffic: dict, on_call=None) -> dict:
    """Run ``call`` back to back until ``seconds`` have passed.

    Returns the window's length (start to the end of the last call), the
    calls completed, and one latency sample per ``calls_per_sample`` calls
    (their mean), all on the host clock.  ``on_call(k)`` wraps each call,
    for the profiler's annotation.
    """
    per = traffic["calls_per_sample"]
    samples: list[float] = []
    done = 0
    start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        for _ in range(per):
            if on_call is None:
                call()
            else:
                with on_call(done):
                    call()
            done += 1
        t1 = time.perf_counter()
        samples.append((t1 - t0) / per)
        if t1 - start >= seconds:
            break
    return dict(window_s=t1 - start, calls=done, samples=samples)
