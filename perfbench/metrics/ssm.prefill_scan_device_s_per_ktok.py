"""Device time of the recurrence alone per thousand real prompt tokens: op
self time of the prefill programs under ``ssm.scan`` ALONE (the chunked
form of a recurrent layer, a delta-rule layer's in-chunk system among it,
and the state's landing in the slot; not the projections, the gate or the
convolution, which ``ssm.prefill_device_s_per_ktok`` counts with it), over
the ``tokens`` of the traced ``bf:engine.prefill_call`` spans / 1,000."""
from perfbench.harness import scopes


def read(run):
    ana = scopes.on_chip(run)
    if not ana or not ana.seconds("prefill ", ("ssm.scan",)):
        return None     # a program without recurrent layers has no such time
    return ana.per_ktok("prefill ", ("ssm.scan",))
