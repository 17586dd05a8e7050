"""Share of the decode step program's device time (the jitted
``_decode_fn`` of ``serve/continuous.py``) spent in its attention
(``models/attention.py`` ``attn_decode``, under
``jax.named_scope("decode_attention")``), over the traced window, in %."""
from bench import program_spans


def read(run):
    prof = program_spans.of(run)
    if prof is None:
        return None
    share = program_spans.scope_share_in(prof, "decode_attention",
                                         program_spans.DECODE_PROGRAM)
    return None if share is None else 100.0 * share
