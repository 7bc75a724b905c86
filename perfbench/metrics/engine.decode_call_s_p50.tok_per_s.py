"""``engine.decode_call_s_p50`` (the benchmark's span around each
ServeEngine.decode call in the window: median) for a cell that does not
report ``token_gap_p90_s``, which that metric moves: the same reading,
listed under the completed tokens per second, which a longer decode call
lowers in a closed loop.  On such a cell it is the one host-span reading
of the call in which the latent cache, the absorbed attention and the
held experts work."""
from perfbench.harness import manifest


def read(run):
    return manifest.load_module(
        "metrics", "engine.decode_call_s_p50").read(run)
