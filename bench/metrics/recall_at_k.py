"""Mean recall@K of the seeded sample of answers against the float64
reference's top K (the IVF lane's stated guarantee)."""


def read(record):
    return record.numbers.get("recall")
