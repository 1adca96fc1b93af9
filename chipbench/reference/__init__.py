"""Plain float32 references of the benchmark's configurations.

Nothing here imports the code under test. ``<config>.py`` holds one
configuration's weights-from-seed, data generator, forward pass and loss,
and its FLOPs per trained sample; ``ops.py`` the shared primitives.
"""
