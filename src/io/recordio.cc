/*!
 * \file recordio.cc
 * \brief native RecordIO implementation + C ABI (see recordio.h).
 */
#include "recordio.h"

#include <cassert>
#include <cstring>

namespace cxxnet_tpu {

static inline uint32_t EncodeLRec(uint32_t cflag, uint32_t length) {
  return (cflag << 29U) | length;
}
static inline uint32_t DecodeFlag(uint32_t rec) {
  return (rec >> 29U) & 7U;
}
static inline uint32_t DecodeLength(uint32_t rec) {
  return rec & ((1U << 29U) - 1U);
}

// ---------------------------------------------------------------- writer

RecordIOWriter::RecordIOWriter(const char *path) {
  fp_ = std::fopen(path, "wb");
}

RecordIOWriter::~RecordIOWriter() { Close(); }

void RecordIOWriter::Close() {
  if (fp_ != nullptr) {
    if (std::fclose(fp_) != 0) fail_ = true;
    fp_ = nullptr;
  }
}

void RecordIOWriter::Put(const void *data, size_t nmemb) {
  if (std::fwrite(data, 4, nmemb, fp_) != nmemb) fail_ = true;
}

void RecordIOWriter::WriteChunk(const uint32_t *data, size_t nword,
                                uint32_t cflag) {
  uint32_t magic = kRecordMagic;
  uint32_t lrec = EncodeLRec(cflag,
                             static_cast<uint32_t>(nword * 4U));
  Put(&magic, 1);
  Put(&lrec, 1);
  if (nword != 0) Put(data, nword);
}

void RecordIOWriter::WriteRecord(const void *buf, size_t size) {
  // copy into a word buffer padded to 4-byte multiple (pad bytes zero)
  size_t nword = (size + 3U) >> 2U;
  std::vector<uint32_t> words(nword, 0);
  std::memcpy(words.data(), buf, size);
  // tail chunk length must encode the true byte size, so we track the
  // byte length of the *last* chunk separately
  // find aligned magic occurrences; split there
  std::vector<size_t> splits;          // word indices equal to magic
  for (size_t i = 0; i < nword; ++i) {
    if (words[i] == kRecordMagic) splits.push_back(i);
  }
  if (splits.empty()) {
    // single whole record: write true byte length
    uint32_t magic = kRecordMagic;
    uint32_t lrec = EncodeLRec(0U, static_cast<uint32_t>(size));
    Put(&magic, 1);
    Put(&lrec, 1);
    size_t n = (size + 3U) >> 2U;
    if (n != 0) Put(words.data(), n);
    return;
  }
  // multi-chunk: payload between magic words; readers re-insert magic
  size_t begin = 0;
  for (size_t k = 0; k <= splits.size(); ++k) {
    size_t endw = (k < splits.size()) ? splits[k] : nword;
    uint32_t cflag;
    if (k == 0) cflag = 1U;                       // start
    else if (k == splits.size()) cflag = 3U;      // end
    else cflag = 2U;                              // middle
    if (k == splits.size()) {
      // final chunk carries the residual byte length
      size_t tail_bytes = size - begin * 4U;
      uint32_t magic = kRecordMagic;
      uint32_t lrec = EncodeLRec(cflag,
                                 static_cast<uint32_t>(tail_bytes));
      Put(&magic, 1);
      Put(&lrec, 1);
      size_t n = (tail_bytes + 3U) >> 2U;
      if (n != 0) Put(words.data() + begin, n);
    } else {
      WriteChunk(words.data() + begin, endw - begin, cflag);
    }
    begin = endw + 1;                             // skip the magic word
  }
}

// ---------------------------------------------------------------- reader

RecordIOReader::RecordIOReader(const char *path, int part_index,
                               int num_parts) {
  fp_ = std::fopen(path, "rb");
  begin_ = end_ = pos_ = 0;
  if (fp_ == nullptr) return;
  std::fseek(fp_, 0, SEEK_END);
  uint64_t fsize = static_cast<uint64_t>(std::ftell(fp_));
  if (num_parts <= 1) {
    begin_ = 0;
    end_ = fsize;
  } else {
    begin_ = fsize * part_index / num_parts;
    end_ = fsize * (part_index + 1) / num_parts;
    begin_ = (begin_ + 3U) & ~3ULL;              // align to words
    end_ = (end_ + 3U) & ~3ULL;
    if (end_ > fsize) end_ = fsize;
  }
  Reset();
}

RecordIOReader::~RecordIOReader() {
  if (fp_ != nullptr) std::fclose(fp_);
}

void RecordIOReader::Reset() {
  if (fp_ == nullptr) return;
  std::fseek(fp_, static_cast<long>(begin_), SEEK_SET);
  pos_ = begin_;
  // scan forward to the first record boundary at/after begin_:
  // a magic word followed by a plausible lrec
  if (begin_ != 0) {
    uint32_t w;
    while (pos_ + 4 <= end_) {
      if (!ReadWord(&w)) return;
      if (w == kRecordMagic) {
        long save = std::ftell(fp_);
        uint32_t lrec;
        if (std::fread(&lrec, 4, 1, fp_) == 1) {
          uint32_t flag = DecodeFlag(lrec);
          if (flag == 0U || flag == 1U) {
            // found a record head: rewind to before magic
            std::fseek(fp_, save - 4, SEEK_SET);
            pos_ -= 4;
            return;
          }
        }
        std::fseek(fp_, save, SEEK_SET);
      }
    }
  }
}

bool RecordIOReader::ReadWord(uint32_t *w) {
  if (std::fread(w, 4, 1, fp_) != 1) return false;
  pos_ += 4;
  return true;
}

bool RecordIOReader::NextRecord(std::string *out) {
  out->clear();
  if (fp_ == nullptr) return false;
  // the shard owner reads any record *starting* before end_
  if (pos_ >= end_) return false;
  bool in_multi = false;
  while (true) {
    uint32_t magic, lrec;
    if (!ReadWord(&magic)) return false;
    if (magic != kRecordMagic) return false;     // corrupt / lost sync
    if (!ReadWord(&lrec)) return false;
    uint32_t cflag = DecodeFlag(lrec);
    uint32_t len = DecodeLength(lrec);
    size_t nword = (len + 3U) >> 2U;
    size_t cur = out->size();
    if (in_multi && cflag != 1U) {
      // rejoin with the magic word that was split out
      out->append(reinterpret_cast<const char *>(&kRecordMagic), 4);
      cur = out->size();
    }
    out->resize(cur + nword * 4U);
    if (nword != 0 &&
        std::fread(&(*out)[cur], 4, nword, fp_) != nword) {
      return false;
    }
    pos_ += nword * 4U;
    out->resize(cur + len);                      // trim pad bytes
    if (cflag == 0U) return true;                // whole record
    if (cflag == 3U) return true;                // end chunk
    in_multi = true;                             // start/middle: continue
  }
}

}  // namespace cxxnet_tpu

// ------------------------------------------------------------------ C ABI

extern "C" {

void *CXNRecordIOWriterCreate(const char *path) {
  auto *w = new cxxnet_tpu::RecordIOWriter(path);
  if (!w->is_open()) {
    delete w;
    return nullptr;
  }
  return w;
}

int CXNRecordIOWriterAppend(void *handle, const char *data,
                            uint64_t size) {
  auto *w = static_cast<cxxnet_tpu::RecordIOWriter *>(handle);
  w->WriteRecord(data, static_cast<size_t>(size));
  return w->HasError() ? -1 : 0;
}

void CXNRecordIOWriterFree(void *handle) {
  delete static_cast<cxxnet_tpu::RecordIOWriter *>(handle);
}

struct CXNReaderState {
  cxxnet_tpu::RecordIOReader reader;
  std::string buf;
  std::string batch;             // NextN: the records end to end
  std::vector<uint64_t> sizes;   // NextN: their lengths
  CXNReaderState(const char *path, int pi, int np)
      : reader(path, pi, np) {}
};

void *CXNRecordIOReaderCreate(const char *path, int part_index,
                              int num_parts) {
  auto *r = new CXNReaderState(path, part_index, num_parts);
  if (!r->reader.is_open()) {
    delete r;
    return nullptr;
  }
  return r;
}

const char *CXNRecordIOReaderNext(void *handle, uint64_t *size) {
  auto *r = static_cast<CXNReaderState *>(handle);
  if (!r->reader.NextRecord(&r->buf)) {
    *size = 0;
    return nullptr;
  }
  *size = r->buf.size();
  return r->buf.data();
}

uint64_t CXNRecordIOReaderNextN(void *handle, uint64_t n,
                                const char **data,
                                const uint64_t **sizes) {
  auto *r = static_cast<CXNReaderState *>(handle);
  r->batch.clear();
  r->sizes.clear();
  while (r->sizes.size() < n && r->reader.NextRecord(&r->buf)) {
    r->batch.append(r->buf);
    r->sizes.push_back(r->buf.size());
  }
  *data = r->batch.data();
  *sizes = r->sizes.data();
  return r->sizes.size();
}

void CXNRecordIOReaderReset(void *handle) {
  static_cast<CXNReaderState *>(handle)->reader.Reset();
}

void CXNRecordIOReaderFree(void *handle) {
  delete static_cast<CXNReaderState *>(handle);
}

}  // extern "C"
