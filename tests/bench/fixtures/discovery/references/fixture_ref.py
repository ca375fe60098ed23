"""A plain reference added as a new file."""

NAME = "fixture reference"
