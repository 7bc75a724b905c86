"""Device time a decode call spends in the state-space layers: op self
time of the decode programs under ``ssm.project`` (a Mamba mixer's two
projections, its gate and grouped norm), ``ssm.conv`` (the convolution
with its kept inputs' read and write) and ``ssm.scan`` (the single step of
the recurrence with the states' read and write), over their module events
in the traced tail."""
from perfbench.harness import scopes

SSM = ("ssm.project", "ssm.conv", "ssm.scan")


def read(run):
    ana = scopes.on_chip(run)
    if not ana or not ana.seconds("decode ", SSM):
        return None     # a program without state-space layers has no such time
    return ana.per_call("decode ", SSM)
