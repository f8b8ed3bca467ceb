"""The control: the reference one precision below what the configuration
states, put in the program's place, must come out not correct.

The configuration states bfloat16 matmul operands (the TPU's default
precision over float32 arrays); the control takes float8 e4m3 operands
under a per-tensor scale.  Here on the CPU at test size, with the cell's
own limits; PERF.md gives its readings on the chip at the cell's size.
"""
from conftest import tiny_cell

SEED = 2 ** 31 + 977


def test_control_is_not_correct():
    import run
    out = {}
    for control in (False, True):
        c = tiny_cell("bert-learn-s64")
        # the BERT level wider than the test size, the expert as it is
        c["cfg"]["cascade"]["tinytf"].update(
            vocab=512, max_len=64, d_model=64, n_heads=4, n_layers=4,
            d_ff=256)
        out[control] = run.run_cell(c, SEED, 1.0, False, check_device=False,
                                    control=control)
    assert out[False]["correct"], out[False]["checks"]
    assert not out[True]["correct"], out[True]["checks"]
