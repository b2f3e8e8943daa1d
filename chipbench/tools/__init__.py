"""Scripts run by hand before a chip call; no run of a cell imports them."""
