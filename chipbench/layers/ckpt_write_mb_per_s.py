"""Entry points: BASELINE.json's second metric, "checkpoint write
GB/s", in MB/s: the bytes of the checkpoint files written in the window
(`bytes` of the `checkpoint.upload` spans) over the time in the
`checkpoint.write` spans."""

from chipbench import spans


def read(run):
    took = sum(s["duration_ns"]
               for s in spans.named(run.spans, "checkpoint.write"))
    uploads = spans.named(run.spans, "checkpoint.upload")
    if not took or not uploads:
        return None
    return sum(s["attrs"]["bytes"] for s in uploads) / 1e6 / (took / 1e9)
