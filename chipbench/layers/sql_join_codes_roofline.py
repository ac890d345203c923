"""Kernels: the joint-code join's share of its roofline (a join whose
build side is no resident lane: a filtered frame, or several keys). As
`sql_join_roofline`, over the program `jit_sqlops_join_codes` and
`sql_join_bytes.join_codes_bytes`. None where no such join reached the
chip."""

from chipbench.layers.sql_join_bytes import join_codes_bytes
from chipbench.layers.sql_join_roofline import share


def read(run):
    return share(run, "sqlops.join_codes", ("n_pad",), join_codes_bytes)
