"""Op library: importing this package registers every op implementation.

Reference parity: paddle/operators/* (one jax function per reference op
kernel family; see SURVEY.md §2.2).
"""
from . import (activations, amp_ops, attention, beam_search, chunked_ce,
               collective_ops, common, control_flow, conv, crf, ctc,
               detection, embedding, loss, math, metrics, misc, moe, norm,
               optim_ops, pool, random, rnn, sequence, ssm, tensor_array,
               tensor_ops)  # noqa: F401
