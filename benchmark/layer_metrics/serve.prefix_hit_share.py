"""Prompt tokens found in the prefix cache over prompt tokens admitted:
the sum of ``matched`` over the sum of ``prompt`` over the admissions in
the window."""

from benchmark import harness


def read(view):
    admissions = harness.load_module(
        "layer_metrics", "serve.queue_wait_ms").admissions
    prompt = sum(admissions(view, "prompt"))
    return 100.0 * sum(admissions(view, "matched")) / prompt \
        if prompt else None
