"""Share of the chip's own op time in the traced window spent in the
latent attention sublayers: ops under the model step's ``mla_q``,
``mla_kv``, ``attn`` (the latent write, the in-loop chunk gather and the
absorbed attention math) and ``mla_out`` scopes
(``chipbench/mla_moe/scopes.py``).  None where a tenth or more of the own
time has no scope or an ambiguous one."""

from chipbench.mla_moe import scopes


def read(run):
    return scopes.share(run, ("mla_q", "mla_kv", "attn", "mla_out"))
