"""Device time a decode call spends attending over the cache: op self time
of the decode programs under ``cache.read``, ``mla.attend``,
``attn.window``, ``attn.full`` (``attn``), over the decode program's
module events in the traced tail.  The projections around it are not in
it (``attn.project``, ``mla.project``)."""
from perfbench.harness import scopes


def read(run):
    ana = scopes.on_chip(run)
    return ana and ana.per_call("decode ", scopes.ATTENTION)
