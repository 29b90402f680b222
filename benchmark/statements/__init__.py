"""Statements, keyed by name: <name>.json holds the text, the tables
read, the columns referenced and (for a keyed statement) the key's
table; <name>.py holds `truth(data, key)`, the plain numpy reference
over the generator's arrays, returning rows as the wire's text."""
