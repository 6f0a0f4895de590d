"""A rehearsal on the CPU runs the page walk's Pallas kernel in interpret
mode, as tests/test_chip_smoke.py does: the same control flow as on the
chip, at a tiny size. Steered from here, not through an option of the
program."""


def apply():
    from paddle_tpu.ops.pallas import paged_attention as pa
    pa._INTERPRET = True
