"""Artifact build: host milliseconds of ``oos.from_dual`` in set-up, up
to the model's arrays being ready (the kernel-mean pass and the centering
terms)."""


def read(ctx):
    return ctx.layer.get("artifact_ms")
