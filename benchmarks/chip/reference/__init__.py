"""Plain references that the check compares the timed path against."""
