"""One module per model family: builds the program's session for a cell."""
