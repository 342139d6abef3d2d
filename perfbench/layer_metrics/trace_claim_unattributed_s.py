"""Layer ``trace-claim``: what is left of ``trace_claim_s`` once the program's
own spans are taken out: on the train path ``_ensure_runtime``, the lazy
imports, the donation tags. A large remainder says where the next span goes;
on the ``jit`` path ``trace_claim_s`` is the sum of its phases and this is 0."""

from perfbench.layer_metrics import _phases

ATTRIBUTED = ("trace", "transforms", "claim", "static_analysis", "codegen", "optimizer_state")


def read(reading):
    whole, spans = reading.spans.get("trace_claim_s"), _phases.seconds(reading, *ATTRIBUTED)
    return None if whole is None or spans is None else whole - spans
