"""A model family added by a file alone: the Llama family, with every call
of each function of the family contract counted in ``CALLS``."""
import collections
import functools

from families import llama

HOOKS = ("model_config", "shapes", "sender_prefill_flops",
         "receiver_prefill_flops", "decode_row_flops", "decode_attn_call",
         "Geometry", "selection", "hop_logits")
CALLS = collections.Counter()


def _recorded(name):
    fn = getattr(llama, name)

    @functools.wraps(fn)
    def call(*args, **kwargs):
        CALLS[name] += 1
        return fn(*args, **kwargs)
    return call


for _name in HOOKS:
    globals()[_name] = _recorded(_name)
