"""One module per kernel: ``counts(model, mix) -> (flops, bytes)`` of one
call of the kernel at the cell's shapes, from the algorithm's operands."""
