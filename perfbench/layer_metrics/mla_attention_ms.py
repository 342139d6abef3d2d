"""Layer ``kernels``: device ms a call in the latent-attention forward calls
(the family ``attn_mla_fwd``: splash calls whose v is narrower than q)."""

from perfbench.reading import kernel_family_table


def read(reading):
    row = kernel_family_table(reading).get("attn_mla_fwd")
    return row["ms"] if row else None
