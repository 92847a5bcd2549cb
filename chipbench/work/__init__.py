"""The work of the components a layer is made of, one module each, found
by name (``chipbench.workcount.component``): ``work/<name>.py``.

Every function takes the widths ``d`` (``chipbench.workcount.Dims``, the
program's ArchConfig fields).  A module gives ``gemms`` and, where the
component has them, the others; one it leaves out counts 0.

- ``gemms(d)``: per op class (the program's ``gemm/<op>`` names), the
  (K, N) of each GEMM one token goes through in one layer;
- ``token_bytes(d)``: bytes one cached token holds;
- ``state_bytes(d)``: bytes of state one row holds, whatever its length;
- ``decode_bytes(d, context)``: bytes a decode step reads and writes for
  one row that holds ``context`` tokens, its new one included;
- ``decode_flops(d, context)``: the flops of that row's token that its
  context sets, GEMMs left out;
- ``prefill_flops(d, prompt_len)``: the same over a causal prompt.
"""
