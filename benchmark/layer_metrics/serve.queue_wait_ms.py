"""Median queue wait over the admissions in the window: ``wait_ms`` on
the ``serve/prefill`` and ``serve/suffix_prefill`` spans (arrival, or
the re-queue after an eviction, to admission).  Says n and p95 on an
earlier line."""

from benchmark import harness, program_spans

ADMISSIONS = ("serve/prefill", "serve/suffix_prefill")


def admissions(view, key):
    return [v for name in ADMISSIONS
            for v in program_spans.stat(view, name, key)]


def read(view):
    waits = admissions(view, "wait_ms")
    if not waits:
        return None
    summary = harness.timing_summary("serve.queue_wait_ms", waits)
    harness.say(summary)
    return summary["median_ms"]
