"""Plain float32 references, one module per family of configurations.
They import nothing of the program."""
