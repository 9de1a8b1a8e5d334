"""Plain float32 references: the model, its loss and gradients, and the
optimizer (AdamW, POGO over VAdam). They import nothing of the program and
read the weights the benchmark made, by the names of the parameter tree."""
