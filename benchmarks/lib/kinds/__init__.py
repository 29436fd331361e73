"""One module a kind of cell, named as the traffic files name the kind
(``-`` written ``_``); each has ``run_cell(run)``."""
