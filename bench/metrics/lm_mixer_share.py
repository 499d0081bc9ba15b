"""Share of the LM prefill and decode programs' device time spent in the
token mixers: ops under the ``attn_mixer`` and ``ssm_mixer`` scopes of
``decoder_layer``, in %."""


def read(ctx):
    t = ctx.trace_summary
    if t is None:
        return None
    total = t.module_s("_prefill")[1] + t.module_s("_decode_step")[1]
    if total <= 0:
        return None
    return 100.0 * (t.scope_s("attn_mixer") + t.scope_s("ssm_mixer")) / total
