"""How the program under test is built for each model family."""
