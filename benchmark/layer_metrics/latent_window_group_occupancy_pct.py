"""``swa_window_group_occupancy_pct`` (live pages of the WINDOW cache group
over its usable pages) under the name it has in the cell whose window group
holds raw latent rows."""
from harness.cells import sibling_reader

read = sibling_reader(__file__, "swa_window_group_occupancy_pct")
