"""How a window drives the program, one module per driver kind."""
