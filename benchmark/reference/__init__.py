"""Plain references: one file per model family, importing nothing of the program."""
