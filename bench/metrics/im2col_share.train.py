"""Share of the chips' busy time in the traced training window spent in
the im2col patch build of the client conv (``kernels/client_conv.py``,
under ``jax.named_scope("im2col")``, its transpose in the backward pass
included), in %.  An operation carries the op_name of its fusion's root;
``python3 -m bench.trace_checks <run>.xplane.pb --scope im2col`` splits
the time by how much of each fusion's body lies in the scope."""
from bench import program_spans


def read(run):
    prof = program_spans.of(run)
    if prof is None:
        return None
    share = program_spans.scope_share(prof, "im2col")
    return None if share is None else 100.0 * share
