"""One module per driver kind; the only files here that import the program."""
