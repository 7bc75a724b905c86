"""Device time of the state-space layers per thousand real prompt tokens:
op self time of the prefill programs under ``ssm.project``, ``ssm.conv``
and ``ssm.scan`` (the chunked scan and the state's landing in the slot),
over the ``tokens`` of the traced ``bf:engine.prefill_call`` spans /
1,000."""
from perfbench.harness import scopes

SSM = ("ssm.project", "ssm.conv", "ssm.scan")


def read(run):
    ana = scopes.on_chip(run)
    if not ana or not ana.seconds("prefill ", SSM):
        return None     # a program without state-space layers has no such time
    return ana.per_ktok("prefill ", SSM)
