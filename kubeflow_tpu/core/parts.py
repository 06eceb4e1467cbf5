"""The parts of a program that the device's time is named by.

Flax puts a module's path into the name of every operation it traces
(``TransformerLM/layers_3/attn/…``), and XLA keeps that name as the
operation's ``op_name``, which a profiler trace shows as ``tf_op``. Work
that runs outside any module — choosing a token, the decode loop's own
state, the loss, the optimizer — has no such name; the engine and the
trainer wrap it in a ``jax.named_scope`` of one of the names below, so
that a trace can say which part of the program spent each operation's
time. A scope is metadata only: the compiled program is the same.

Flax's own module names (``embed``, ``attn``, ``mlp``, ``experts``,
``ln1``, ``ln2``, ``ln_f``, ``unembed``, ``mlm_transform``, …) name the
rest, and no scope is put around a module."""

from __future__ import annotations

#: token choice: the RNG splits, the draw and the greedy pick
SAMPLE = "sample"
#: the rows' bookkeeping around the model: positions, liveness, the
#: generation count, the decode loop's carried and stacked outputs
CARRY = "carry"
#: the admission epoch's merge of the carry (``LMEngine._merge``)
MERGE = "merge"
#: the head's own work outside its modules: the wanted positions picked
#: before ``ln_f`` and ``unembed``
HEAD = "head"
#: the routing counters an expert model's serving programs return
MOE_STATS = "moe_stats"
#: a training step's objective: the masking of its inputs and everything
#: after the logits up to the scalar
LOSS = "loss"
#: a training step's parameter update
OPTIMIZER = "optimizer"

PARTS = (SAMPLE, CARRY, MERGE, HEAD, MOE_STATS, LOSS, OPTIMIZER)
