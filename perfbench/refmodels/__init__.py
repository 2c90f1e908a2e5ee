"""Frozen plain references of the configurations' models (flax, no kernels).

Each module offers ``build(sizes, dtype) -> (module, example_input)`` and
``loss(module, variables, batch, key, train) -> (loss, new_model_state,
new_carry)``; ``sizes`` is the configuration file's ``sizes`` group.
Nothing here imports the program.
"""
