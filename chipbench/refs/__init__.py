"""Plain references: the data generator and the estimands, kept apart
from the program under test so that a change to the program cannot move
them."""
