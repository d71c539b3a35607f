"""``decode_program_ms`` under the name it has in the cell whose end-to-end metric it
moves there (one entry of ``per_layer`` names one metric it moves)."""
from harness.cells import sibling_reader

read = sibling_reader(__file__, "decode_program_ms")
