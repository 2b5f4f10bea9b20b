"""Plain references the correctness check compares the program with."""
