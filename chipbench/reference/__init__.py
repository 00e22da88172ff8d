"""Plain references that decide ``correct``: written from the published
definitions, importing nothing of the program and taking nothing it made."""
