"""How the program's compiled programs and kernel appear in a device
trace. The program gives them no stable names yet, so these patterns match
what a TPU v5e trace shows today: the fused step is the XLA module
``jit__fused(<hash>)`` and the Mosaic paged-attention kernel is the custom
call ``%closed_call.N = bf16[B,KV,rep,hd] custom-call(...)`` inside it
(the step's other custom calls are nanosecond buffer markers); inside
the ``shard_map`` of a step sharded over several chips the same call is
``%shard_map.N = ... custom-call(...)``."""

STEP_MODULE = r"^jit__fused\("
PAGED_KERNEL = r"^%(?:closed_call|shard_map)\.\d+ = .*custom-call\("
